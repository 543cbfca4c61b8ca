"""Read what the limits of ``correct`` are set from, for a ``fused_sparse``
cell.

    python3 benchmark/calibrate_sparse.py --workload <cell> --seeds 2 --control_seeds 2

``benchmark/calibrate_seq.py`` for the sparse-attention policy. In one
process, at the cell's own size, for each seed: the program's first update
(the set-up a run makes), its learner's choices and its decode through the
carry against the reference's, all eight numbers. On the first
``--control_seeds`` seeds also the controls:

- ``fp8_weights``: the program itself, its matrices rounded to float8
  e4m3's mantissa, against the float32 reference from the unrounded weights;
- ``topk_1024``: the program alone keeping half as many keys (a fault, not a
  precision): has to fail ``select_flip_share`` and ``logit_gap``;
- ``fp8`` (asked for by name): the reference with its matrix operands in
  float8, playing the program's actions with the program's choices, against
  the float32 reference doing the same.

Prints every number for each and writes them to
``chiprun_out/calibrate-<cell>.json``. The benchmark's runs never call this;
their own printed numbers are the sound seeds.
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

from benchmark import check_sparse  # noqa: E402
from benchmark.run import NoChip, claim_chips  # noqa: E402
from benchmark.spec import Benchmark  # noqa: E402

NUMBERS = ("loss_gap", "first_grad_norm_gap", "param_delta_norm_gap",
           "state_mismatch_share", "action_flip_share") + check_sparse.NUMBERS
NO_LIMITS = dict.fromkeys(NUMBERS, float("inf"))
CONTROLS = ("fp8_weights", "topk_1024", "fp8")


def readings(bench, cell, config, devices, seed, controls, sound=True):
    driver = bench.driver(config["driver"])

    def rows(side, reference_side, session):
        return session.compare(side, reference_side, NO_LIMITS, NO_LIMITS)

    out = {"seed": seed}
    if sound or "fp8" in controls:
        session = driver.setup(cell, config, devices, seed)
        session.release()
        sound_ref = session.reference_readings(actions=session.program["actions"])
        out["program"] = rows(session.program, sound_ref, session)
        if "fp8" in controls:
            lowered = session.reference_readings(
                lower="fp8", actions=session.program["actions"])
            out["fp8"] = rows(dict(lowered, actions=session.program["actions"]),
                              sound_ref, session)
        del session, sound_ref
        gc.collect()
    for control in driver.CONTROLS:
        if control in controls:
            other = driver.setup(cell, config, devices, seed, control=control)
            other.release()
            out[control] = rows(
                other.program,
                other.reference_readings(actions=other.program["actions"]),
                other)
            del other
            gc.collect()
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=2)
    p.add_argument("--control_seeds", type=int, default=2)
    p.add_argument("--first_seed", type=int, default=3_100_000_001)
    p.add_argument("--controls", nargs="*", choices=CONTROLS,
                   default=CONTROLS[:2])
    p.add_argument("--no_sound", action="store_true",
                   help="the controls alone (the cell's own runs are the sound seeds)")
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
                     args.controls if i < args.control_seeds else (),
                     sound=not args.no_sound)
        rows.append(r)
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
        print(f"{number}:"
              + (f" sound max {sound[-1]:.5g} median {sound[len(sound) // 2]:.5g}"
                 if sound else "")
              + "".join(f" | {c} min {values(c)[0]:.5g}" for c in CONTROLS if values(c)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
