"""Read what the limits of ``correct`` are set from, for the Mamba-2 and
sparse-expert hybrid's cell.

    python3 benchmark/calibrate_nemotronh.py --workload <cell> --seeds 1 --controls fp8_weights state_bf16 half_batch no_reset

``benchmark/calibrate_seq.py`` for ``drivers/fused_nemotronh.py``. In one
process, at the cell's own size, for each seed: the program's first update
(the set-up a run makes) and its decode through the carry against the
reference's, all six numbers of ``check_seq.py`` and ``check_lm.py``'s
``route_flip_share``; then each control asked for, a precision below a
stated one:

- ``fp8_weights``: the program itself, its matrices rounded to float8
  e4m3's mantissa, against the float32 reference from the unrounded weights;
- ``state_bf16``: the program alone with the Mamba-2 recurrence's state kept
  in bfloat16, in the decode's carry and between the learner's chunks: a
  precision below the stated one in the new mechanism itself;

and each planted fault, which the numbers no precision moves are held
against (``drivers/fused_nemotronh.py``): ``half_batch`` (the gradient's and
the parameters' norms) and ``no_reset`` (``logit_gap``). Every side is
compared UNDER THE CELL'S COMMITTED LIMITS, as a run's ``check`` compares,
and the line says by which numbers it came out not correct.

Prints every number for each beside its limit and writes them to
``chiprun_out/calibrate-<cell>.json``. The benchmark's runs never call this:
its own runs are the sound seeds (each prints its six numbers), and
``--no_sound`` leaves the sound session out here. A session keeps host
copies of 667 M parameters three times over: one seed a process.
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

from benchmark.run import NoChip, claim_chips  # noqa: E402
from benchmark.spec import Benchmark  # noqa: E402

CONTROLS = ("fp8_weights", "state_bf16")
FAULTS = ("half_batch", "no_reset")


def readings(bench, cell, config, devices, seed, sides, sound=True):
    """{side: the seven rows, each under the cell's committed limit} for the
    sound program, each control and each planted fault asked for. A fault
    plays the sound program's rollout, so beside a sound session it is
    compared with that session's reference (``no_reset`` is that session's
    own decode, run again without the reset)."""
    driver = bench.driver(config["driver"])
    out = {"seed": seed}
    sound_reference = None
    for side in (("program",) if sound else ()) + tuple(sides):
        if side == "no_reset" and sound:
            continue  # read on the sound session, below
        session = driver.setup(
            cell, config, devices, seed,
            control=None if side == "program" else side)
        session.release()
        if side == "half_batch" and sound_reference is not None:
            session.decode_through_the_carry()
            reference_side = sound_reference
        else:
            reference_side = session.reference_readings(
                actions=session.program["actions"])
        out[side] = session.compare(session.program, reference_side)
        if side == "program":
            sound_reference = reference_side
            if "no_reset" in sides:
                out["no_reset"] = session.compare(
                    dict(session.program,
                         decode_logits=session.decode_without_a_reset()),
                    reference_side)
        del session
        gc.collect()  # a session's compiled step and host copies before the next
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=1)
    p.add_argument("--first_seed", type=int, default=3_400_000_001)
    p.add_argument("--controls", nargs="*", choices=CONTROLS + FAULTS,
                   default=CONTROLS)
    p.add_argument("--no_sound", action="store_true")
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
        r = readings(bench, cell, config, devices, seed, args.controls,
                     sound=not args.no_sound)
        rows.append(r)
        for side in ("program", *CONTROLS, *FAULTS):
            if side in r:
                over = [x["number"] for x in r[side] if not x["ok"]]
                print(f"seed {seed} {side:11s} "
                      + (f"NOT correct by {' '.join(over)}" if over else
                         "correct under the cell's limits"), flush=True)
                print(f"seed {seed} {side:11s} " + "  ".join(
                    f"{x['number']}={x['value']:.5g}/{x['limit']:.5g}"
                    for x in r[side]), flush=True)
                print(f"seed {seed} {side:11s} details: " + " | ".join(
                    f"{x['number']}: {x['detail'][-110:]}" for x in r[side]), flush=True)
        print(f"seed {seed}: {time.monotonic() - t0:.1f} s", flush=True)
        with open(os.path.join(
                out_dir, f"calibrate-{args.workload}-{args.first_seed}.json"), "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
