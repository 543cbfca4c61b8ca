"""Read what the limits of ``correct`` are set from, for a ``fused_lm`` cell.

    python3 benchmark/calibrate_lm.py --workload <cell> --seeds 12 --control_seeds 2

``benchmark/calibrate.py`` for the token-sequence policy. In one process, at
the cell's own size, for each seed: the program's first update(s) (the
set-up a run makes) against the reference's, all seven numbers. On the
first ``--control_seeds`` seeds also the controls, the precision below the
configuration's bfloat16 in the program's place:

- ``fp8_weights``: the program itself, its matrices rounded through float8
  e4m3 (``drivers/fused_lm.py``), against the float32 reference from the
  unrounded weights;
- ``fp8``: the reference with its matrix operands in float8, forward and
  backward, playing the program's actions and routes, against the float32
  reference doing the same.

``--decode_check`` also decodes the first chunk's episodes token by token
through the policy's carry and compares every position's logits with the
learner's unroll (the two forwards of the one model, at the cell's widths).
Prints every number for each and writes them to
``chiprun_out/calibrate-<cell>.json``. The benchmark's runs never call this.
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

from benchmark import check, check_lm  # noqa: E402
from benchmark.run import NoChip, claim_chips  # noqa: E402
from benchmark.spec import Benchmark  # noqa: E402

NUMBERS = ("loss_gap", "first_grad_norm_gap", "param_delta_norm_gap",
           "state_mismatch_share", "action_flip_share") + check_lm.NUMBERS
NO_LIMITS = dict.fromkeys(NUMBERS, float("inf"))
CONTROLS = ("fp8_weights", "fp8")


def decode_gap(session) -> dict:
    """Decoding the first chunk's episodes through the carry against the
    unroll's logits, a token's gap its largest logit gap over the unroll's
    largest logit: the median token, the 90th percentile, the worst. The
    two forwards choose their own routes, so a token near a routing tie
    (and the few after it) reads a whole expert apart: the worst token is
    that, the median is rounding."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    model, tokens = session.model, jnp.asarray(session.program["forward"]["tokens"])
    params = session.start_params()

    def decode(params, tokens):
        served = model.rollout_params(params)

        def one(carry, tok_t):
            held, fresh = carry
            out, held = model.step(served, tok_t, held, fresh)
            return (held, jnp.zeros_like(fresh)), out.logits

        carry = (model.init_carry(tokens.shape[0]),
                 jnp.ones(tokens.shape[0], bool))
        _, logits = jax.lax.scan(one, carry, jnp.swapaxes(tokens, 0, 1))
        return jnp.swapaxes(logits, 0, 1)

    got = np.asarray(jax.jit(decode)(params, tokens))
    want = session.program["forward"]["logits"]
    gap = np.abs(got - want).max(axis=-1) / np.abs(want).max()
    return {"median": float(np.median(gap)), "p90": float(np.percentile(gap, 90)),
            "worst": float(gap.max()),
            "first_64_positions_worst": float(gap[:, :64].max())}


def readings(bench, cell, config, devices, seed, controls, decode_check):
    driver = bench.driver(config["driver"])

    def rows(side, reference_side, session):
        return session.compare(side, reference_side, NO_LIMITS, NO_LIMITS)

    session = driver.setup(cell, config, devices, seed)
    session.release()
    sound_ref = session.reference_readings(actions=session.program["actions"])
    out = {"seed": seed, "program": rows(session.program, sound_ref, session)}
    if decode_check:
        out["decode_gap"] = decode_gap(session)
    if "fp8" in controls:
        lowered = session.reference_readings(
            lower="fp8", actions=session.program["actions"])
        # the float8 side in the program's place, with the program's states
        # (it played the same actions) and what the float32 side says of them
        side = dict(lowered, actions=session.program["actions"])
        out["fp8"] = rows(side, sound_ref, session)
    if "fp8_weights" in controls:
        control = driver.setup(cell, config, devices, seed, control="fp8_weights")
        control.release()
        out["fp8_weights"] = rows(
            control.program,
            control.reference_readings(actions=control.program["actions"]),
            control)
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--control_seeds", type=int, default=2)
    p.add_argument("--first_seed", type=int, default=2_600_000_001)
    p.add_argument("--controls", nargs="*", choices=CONTROLS, default=CONTROLS)
    p.add_argument("--decode_check", type=int, default=1,
                   help="seeds on which to compare decode with unroll")
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
                     i < args.decode_check)
        rows.append(r)
        for side in ("program", *CONTROLS):
            if side in r:
                print(f"seed {seed} {side:11s} " + "  ".join(
                    f"{x['number']}={x['value']:.5g}" for x in r[side]), flush=True)
                print(f"seed {seed} {side:11s} details: " + " | ".join(
                    f"{x['number']}: {x['detail'][-90:]}" for x in r[side]), flush=True)
        if "decode_gap" in r:
            print(f"seed {seed} decode through the carry against the unroll, "
                  f"logit gap a token: {r['decode_gap']}", flush=True)
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
