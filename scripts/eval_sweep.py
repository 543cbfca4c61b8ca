"""Independent re-evaluation sweep over a run's kept checkpoints.

The north-star verification protocol (VERDICT r3 Missing #1): in-training
evals are noisy (64-ep reads sit +-0.5 around fresh-seed 128-ep re-evals),
so the claimed crossing must come from INDEPENDENT re-evals of kept
checkpoints — fresh seeds, >=128 episodes, a horizon covering full episodes.

Usage (ONE process, one chip claim — a chip belongs to one process, see
docs/OPERATIONS.md):
    python scripts/eval_sweep.py --env jax:pong \
        --load runs/ns_r4_a/checkpoints [--steps 40000,44800,...] \
        --nr_eval 128 --max_steps 10000 --threshold 18 \
        --out runs/ns_r4_a/eval_sweep.json

Walks every kept step (ascending) unless --steps narrows it, evaluates each
with the on-device greedy Evaluator on a seed stream DISJOINT from
training's (integer seeds 777000+step vs training's 1000+epoch), and writes
one JSON with per-step means plus the earliest step clearing --threshold.
Exits nonzero when any checkpoint's eval raised (the JSON still holds every
eval that succeeded, and says which did not).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from distributed_ba3c_tpu.train.eval_tools import make_checkpoint_evaluator


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--env", default="jax:pong")
    ap.add_argument("--load", required=True)
    ap.add_argument("--steps", default=None,
                    help="comma-separated step subset (default: all kept)")
    ap.add_argument("--nr_eval", type=int, default=128)
    ap.add_argument("--max_steps", type=int, default=10000)
    ap.add_argument("--threshold", type=float, default=None)
    ap.add_argument("--fc_units", type=int, default=512)
    ap.add_argument("--out", default=None)
    ap.add_argument("--tpu_lock", default="wait", choices=["wait", "fail", "off"])
    args = ap.parse_args()

    from distributed_ba3c_tpu.utils.devicelock import guard_tpu

    guard_tpu("eval_sweep", mode=args.tpu_lock)  # held for process lifetime

    from distributed_ba3c_tpu.utils.backend import (
        configure_compile_cache,
        log_device_info,
    )

    configure_compile_cache()
    device = log_device_info()

    mgr, target, evaluate, n_eval = make_checkpoint_evaluator(
        args.env, args.load, args.nr_eval, args.max_steps, args.fc_units
    )
    steps = (
        [int(s) for s in args.steps.split(",")]
        if args.steps
        else mgr.all_steps
    )
    if not steps:
        raise SystemExit(f"no checkpoints recorded under {args.load}")

    out = args.out or f"{args.load}/../eval_sweep.json"
    results = []
    earliest = None

    def write_summary(complete):
        summary = {
            "load": args.load,
            "nr_eval_requested": args.nr_eval,
            "n_eval_envs": n_eval,
            "max_steps": args.max_steps,
            "threshold": args.threshold,
            "seed_stream": "777000+step, disjoint from training's 1000+epoch",
            "device": device,
            "results": results,
            "earliest_at_threshold": earliest,
            "sweep_complete": complete,
        }
        tmp = out + ".tmp"
        with open(tmp, "w") as f:
            json.dump(summary, f, indent=1)
        os.replace(tmp, out)

    for step in steps:
        try:
            state = mgr.restore(target, step)
            # integer seed stream provably disjoint from training's
            # 1000+epoch
            mean, mx, n = evaluate(state.params, 777000 + step)
        except Exception as e:
            # one bad checkpoint (or a device error) must not discard the
            # evals already done — the sweep IS the verification artifact;
            # record the failure, go on, and exit nonzero at the end
            rec = {"step": step, "error": f"{type(e).__name__}: {e}"}
            results.append(rec)
            print(json.dumps(rec), flush=True)
            write_summary(complete=False)
            continue
        # n==0 => mean/max are fill values (-inf is not even valid JSON)
        rec = {"step": step,
               "eval_mean": round(mean, 3) if n > 0 else None,
               "eval_max": round(mx, 2) if n > 0 else None,
               "episodes": n}
        results.append(rec)
        print(json.dumps(rec), flush=True)
        # long rallies can leave a few envs unfinished at the horizon
        # (round 3's final ckpt re-eval completed 127/128); demand near-full
        # completion and report the exact count in the record
        if (
            args.threshold is not None
            and earliest is None
            and n >= max(1, int(0.95 * n_eval))
            and mean >= args.threshold
        ):
            earliest = rec
        # incremental write: a crash at checkpoint k keeps evals 1..k
        write_summary(complete=False)
    n_errors = sum("error" in r for r in results)
    write_summary(complete=not n_errors)
    print(f"wrote {out}", flush=True)
    if args.threshold is not None:
        print(
            "earliest independently-verified >= %.4g: %s"
            % (args.threshold, earliest or "NONE in sweep"),
            flush=True,
        )
    if n_errors:
        print(f"{n_errors} checkpoint eval(s) raised", file=sys.stderr)
    return 1 if n_errors else 0


if __name__ == "__main__":
    sys.exit(main())
