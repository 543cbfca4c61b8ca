"""Read what the limits of ``correct`` are set from, for the latent-attention
policy's cell.

    python3 benchmark/calibrate_xing4.py --workload <cell> --first_seed <n> --controls fp8_weights streams_bf16 sinkhorn_5 yarn_off half_batch

``benchmark/calibrate_nemotronh.py`` for ``drivers/fused_xing4.py``. In one
process, at the cell's own size, for one seed: the program's first update
(the set-up a run makes) and its decode through the carry against the
reference's, all six numbers of ``check_seq.py`` and ``check_lm.py``'s
``route_flip_share``; then each control asked for, a precision below a
stated one:

- ``fp8_weights``: the program itself, its matrices rounded to float8
  e4m3's mantissa, against the float32 reference from the unrounded weights;
- ``streams_bf16``: the program alone with the residual streams and the
  hyper-connections' mappings in bfloat16: a precision below the stated one
  inside the new mechanism itself;

and each planted fault (``drivers/fused_xing4.py``): ``sinkhorn_5`` (5
iterations of the projection's 20), ``yarn_off`` (the decode alone rotates
by the unscaled frequencies: ``logit_gap``'s; beside a sound session it is
that session's own decode, run again with the fault) and ``half_batch``
(the gradient's and the parameters' norms). Every side is compared UNDER
THE CELL'S COMMITTED LIMITS, as a run's ``check`` compares, and the line
says by which numbers it came out not correct.

Prints every number for each beside its limit and writes them to
``chiprun_out/calibrate-<cell>-<seed>.json``. The benchmark's runs never
call this: its own runs are the sound seeds (each prints its eight numbers),
and ``--no_sound`` leaves the sound session out here. A session keeps host
copies of 671 M parameters three times over: one seed a process.
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

CONTROLS = ("fp8_weights", "streams_bf16")
FAULTS = ("sinkhorn_5", "yarn_off", "half_batch")


def readings(bench, cell, config, devices, seed, sides, sound=True,
             report=None):
    """{side: the eight rows, each under the cell's committed limit} for the
    sound program, each control and each planted fault asked for.
    ``half_batch`` plays the sound program's rollout, so beside a sound
    session it is compared with that session's reference; ``yarn_off`` is
    then that session's own decode, run again with the fault. ``report(side,
    rows)`` is told each side as it is read (a call cut at its limit has
    said what it got to)."""
    driver = bench.driver(config["driver"])
    out = {"seed": seed}
    sound_reference = None
    report = report or (lambda side, rows: None)
    for side in (("program",) if sound else ()) + tuple(sides):
        if side == "yarn_off" and sound:
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
        report(side, out[side])
        if side == "program":
            sound_reference = reference_side
            if "yarn_off" in sides:
                out["yarn_off"] = session.compare(
                    dict(session.program,
                         decode_logits=session.decode_with_yarn_off()),
                    reference_side)
                report("yarn_off", out["yarn_off"])
        del session
        gc.collect()  # a session's compiled step and host copies before the next
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--first_seed", type=int, default=3_400_000_001)
    p.add_argument("--controls", nargs="*", choices=CONTROLS + FAULTS,
                   default=CONTROLS + FAULTS)
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
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    seed = args.first_seed
    t0 = time.monotonic()
    path = os.path.join(out_dir, f"calibrate-{args.workload}-{seed}.json")
    told = {"seed": seed}

    def report(side, rows):
        over = [x["number"] for x in rows if not x["ok"]]
        print(f"seed {seed} {side:12s} "
              + (f"NOT correct by {' '.join(over)}" if over else
                 "correct under the cell's limits"), flush=True)
        print(f"seed {seed} {side:12s} " + "  ".join(
            f"{x['number']}={x['value']:.5g}/{x['limit']:.5g}"
            for x in rows), flush=True)
        print(f"seed {seed} {side:12s} details: " + " | ".join(
            f"{x['number']}: {x['detail'][-110:]}" for x in rows), flush=True)
        told[side] = rows
        with open(path, "w") as f:
            json.dump([told], f, indent=1)

    readings(bench, cell, config, devices, seed, args.controls,
             sound=not args.no_sound, report=report)
    print(f"seed {seed}: {time.monotonic() - t0:.1f} s", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
