"""Standalone greedy evaluation of a fused-trainer checkpoint.

Usage:
    python scripts/eval_fused.py --env jax:pong \
        --load runs/pong_northstar/checkpoints [--step N] \
        --nr_eval 32 --max_steps 20000

Loads the TrainState from orbax, runs the on-device greedy Evaluator with a
horizon long enough for full episodes, prints one JSON line.
"""

from __future__ import annotations

import argparse
import json
import sys

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from distributed_ba3c_tpu.train.eval_tools import make_checkpoint_evaluator


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--env", default="jax:pong")
    ap.add_argument("--load", required=True)
    ap.add_argument("--step", type=int, default=None)
    ap.add_argument("--best", action="store_true", help="use the best-marked step")
    ap.add_argument("--nr_eval", type=int, default=32)
    ap.add_argument("--max_steps", type=int, default=20000)
    ap.add_argument("--fc_units", type=int, default=512)
    ap.add_argument("--tpu_lock", default="wait", choices=["wait", "fail", "off"])
    args = ap.parse_args()

    from distributed_ba3c_tpu.utils.devicelock import guard_tpu

    _lock = guard_tpu("eval_fused", mode=args.tpu_lock)  # noqa: F841
    from distributed_ba3c_tpu.utils.backend import configure_compile_cache

    configure_compile_cache()

    mgr, target, evaluate, _ = make_checkpoint_evaluator(
        args.env, args.load, args.nr_eval, args.max_steps, args.fc_units
    )
    step = args.step
    if args.best and step is None:
        step = mgr.best_step
        if step is None:
            raise SystemExit(
                "--best: no best-marked checkpoint in this run "
                "(eval never improved); pass --step or drop --best"
            )
    state = mgr.restore(target, step)

    mean, mx, n = evaluate(state.params, 123)
    print(
        json.dumps(
            {
                "env": args.env,
                "ckpt_step": int(state.step),
                # n==0: no episode finished inside the horizon — 0.0/-inf
                # would masquerade as scores (and -Infinity is invalid JSON)
                "eval_mean_score": round(mean, 3) if n > 0 else None,
                "eval_max_score": round(mx, 3) if n > 0 else None,
                "episodes": n,
                "max_steps": args.max_steps,
            }
        )
    )
    if n == 0:
        raise SystemExit(
            "no episode completed within --max_steps; raise the horizon"
        )


if __name__ == "__main__":
    main()
