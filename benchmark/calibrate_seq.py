"""Read what the limits of ``correct`` are set from, for a ``fused_seq`` cell.

    python3 benchmark/calibrate_seq.py --workload <cell> --seeds 12 --control_seeds 2

``benchmark/calibrate_lm.py`` for the dense token-sequence policy. In one
process, at the cell's own size, for each seed: the program's first update
(the set-up a run makes) and its decode through the carry against the
reference's, all six numbers. On the first ``--control_seeds`` seeds also
the controls:

- ``fp8_weights``: the program itself, its matrices rounded to float8
  e4m3's mantissa (``drivers/fused_seq.py``), against the float32 reference
  from the unrounded weights;
- ``fp8``: the reference with its matrix operands in float8, forward and
  backward, playing the program's actions, against the float32 reference
  doing the same;
- ``window_256``: the program alone with a window of 256 (a fault, not a
  precision): has to fail ``logit_gap``.

Prints every number for each and writes them to
``chiprun_out/calibrate-<cell>.json``. The benchmark's runs never call this.

Host memory: a session keeps host copies of 697 M parameters (its start, the
first gradient, the change: up to 14 GB at once), and what one releases does
not all go back to the system: one seed with its three controls fits a
one-chip machine's 40 GiB, a second seed after it did not (my chip run, PR
31). So: ``--seeds 1`` a process where controls run, and the benchmark's own
runs (one session a process) as the sound seeds.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import check_seq  # noqa: E402
from benchmark.run import NoChip, claim_chips  # noqa: E402
from benchmark.spec import Benchmark  # noqa: E402

NUMBERS = ("loss_gap", "first_grad_norm_gap", "param_delta_norm_gap",
           "state_mismatch_share", "action_flip_share") + check_seq.NUMBERS
NO_LIMITS = dict.fromkeys(NUMBERS, float("inf"))
CONTROLS = ("fp8_weights", "fp8", "window_256")


def readings(bench, cell, config, devices, seed, controls):
    driver = bench.driver(config["driver"])

    def rows(side, reference_side, session):
        return session.compare(side, reference_side, NO_LIMITS, NO_LIMITS)

    session = driver.setup(cell, config, devices, seed)
    session.release()
    sound_ref = session.reference_readings(actions=session.program["actions"])
    out = {"seed": seed, "program": rows(session.program, sound_ref, session)}
    if "fp8" in controls:
        lowered = session.reference_readings(
            lower="fp8", actions=session.program["actions"])
        # the float8 side in the program's place, with the program's states
        # (it played the same actions) and what the float32 side says of them
        side = dict(lowered, actions=session.program["actions"])
        out["fp8"] = rows(side, sound_ref, session)
    for control in ("fp8_weights", "window_256"):
        if control in controls:
            other = driver.setup(cell, config, devices, seed, control=control)
            other.release()
            out[control] = rows(
                other.program,
                other.reference_readings(actions=other.program["actions"]),
                other)
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--control_seeds", type=int, default=2)
    p.add_argument("--first_seed", type=int, default=3_100_000_001)
    p.add_argument("--controls", nargs="*", choices=CONTROLS, default=CONTROLS)
    args = p.parse_args(argv)

    bench = Benchmark()
    cell = bench.cell(args.workload)
    config = bench.config(cell["config"])
    try:
        devices, _ = claim_chips(bench, cell)
    except NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    rows = []
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.monotonic()
        r = readings(bench, cell, config, devices, seed,
                     args.controls if i < args.control_seeds else ())
        rows.append(r)
        gc.collect()  # a seed's compiled step (0.4 GB of code) before the next
        for side in ("program", *CONTROLS):
            if side in r:
                print(f"seed {seed} {side:11s} " + "  ".join(
                    f"{x['number']}={x['value']:.5g}" for x in r[side]), flush=True)
                print(f"seed {seed} {side:11s} details: " + " | ".join(
                    f"{x['number']}: {x['detail'][-110:]}" for x in r[side]), flush=True)
        print(f"seed {seed}: {time.monotonic() - t0:.1f} s", flush=True)
        with open(os.path.join(out_dir, f"calibrate-{args.workload}.json"), "w") as f:
            json.dump(rows, f, indent=1)
    for number in NUMBERS:
        def values(side):
            return sorted(x["value"] for r in rows for x in r.get(side, ())
                          if x["number"] == number)
        sound = values("program")
        print(f"{number}: sound max {sound[-1]:.5g} median {sound[len(sound) // 2]:.5g}"
              + "".join(f" | {c} min {values(c)[0]:.5g}" for c in CONTROLS if values(c)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
