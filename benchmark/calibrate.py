"""Read what the limits of ``correct`` are set from, on the chip.

    python3 benchmark/calibrate.py --workload <cell> --seeds 12 --control_seeds 3

In one process, at the cell's own size, for each seed: the program's first
updates (the same set-up a run makes) and the reference's; on the first
``--control_seeds`` seeds also the controls ``--controls`` names, the
precision below the configuration's bfloat16 put in the program's place:

- ``int8``: the program itself, its step built with the rollout forward
  served from its own int8 table (``drivers/fused.py``), while the actions
  handed to the reference still come from the unchanged rollout;
- ``fp8``: the reference with its matrix operands in float8, forward and
  backward, drawing its own actions, which the float32 reference then plays.

Prints every number compared for each and writes them to
``chiprun_out/calibrate-<cell>.json``. The benchmark's runs never call this;
``PERF.md`` records the readings each limit was set from.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import check  # noqa: E402
from benchmark.run import NoChip, claim_chips  # noqa: E402
from benchmark.spec import Benchmark  # noqa: E402

NUMBERS = ("loss_gap", "first_grad_norm_gap", "param_delta_norm_gap",
           "state_mismatch_share", "action_flip_share")
NO_LIMITS = dict.fromkeys(NUMBERS, float("inf"))
CONTROLS = ("int8", "fp8")


def readings(bench, cell, config, devices, seed, controls):
    """-> {"seed", "program": rows, <control>: rows, ...} for one seed."""
    driver = bench.driver(config["driver"])

    def against_reference(side, session):
        return check.compare(
            side, session.reference_readings(actions=side["actions"]), NO_LIMITS
        )

    session = driver.setup(cell, config, devices, seed)
    session.release()
    out = {"seed": seed, "program": against_reference(session.program, session)}
    if "int8" in controls:
        lowered = driver.setup(cell, config, devices, seed, control=True)
        lowered.release()
        out["int8"] = against_reference(lowered.program, lowered)
    if "fp8" in controls:
        out["fp8"] = against_reference(
            session.reference_readings(lower="fp8"), session
        )
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--control_seeds", type=int, default=3)
    p.add_argument("--first_seed", type=int, default=2_200_000_001)
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
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.monotonic()
        r = readings(bench, cell, config, devices, seed,
                     args.controls if i < args.control_seeds else ())
        rows.append(r)
        for side in ("program", *CONTROLS):
            if side in r:
                print(f"seed {seed} {side:8s} " + "  ".join(
                    f"{x['number']}={x['value']:.5g} ({x['detail'][-60:]})"
                    for x in r[side]), flush=True)
        print(f"seed {seed}: {time.monotonic() - t0:.1f} s", flush=True)
    for number in NUMBERS:
        def values(side):
            return sorted(x["value"] for r in rows for x in r.get(side, ())
                          if x["number"] == number)
        sound = values("program")
        print(f"{number}: sound max {sound[-1]:.5g} median {sound[len(sound) // 2]:.5g}"
              + "".join(f" | {c} min {values(c)[0]:.5g}" for c in CONTROLS if values(c)))
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"calibrate-{args.workload}.json"), "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
