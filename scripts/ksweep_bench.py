"""K-sweep at the BENCH shape: env-steps/s/chip vs --steps_per_dispatch K.

Replaces round 4's contaminated sweep (PERF.md) with a committed
methodology: delegates to ``bench.bench_fused`` so the measurement policy
(state creation, warmup-and-drain, 3 fully-synced windows, best window
wins) lives in exactly one place — for each K the window's ``iters``
updates run as ``iters/K`` dispatches of one K-step scanned program.
Run on an idle chip — the TPU-claim mutex queues (bounded) or refuses if
another local process holds it.

``--n_envs`` takes a comma list to capture SHARD SHAPES (VERDICT r5 Next
#1): the RESULTS.md v4-8 wall-clock conversion shards the solving batch
(32 envs x 20) across 4 chips, so each chip actually runs an 8-env shard
— a shape whose rate was never measured (the e8 ladder row saw 16-env
batches drop to ~38k). ``--n_envs 8,16`` measures those shard rates so
the headline conversion can be restated from data instead of assuming
the 32-env single-chip rate survives the shard split:

  python scripts/ksweep_bench.py --n_envs 8,16 --ks 1,20 --total 200

Prints per-(shape,K) diagnostics on stderr and ONE JSON line on stdout
(the repo's bench-tooling contract, utils/devicelock.py). Single-shape
runs keep the legacy top-level ``per_chip_by_K``/``windows_by_K`` keys
(runs/ksweep_r5.json schema); every run also emits the shape-keyed
``rows``.

Usage: python scripts/ksweep_bench.py [--ks 1,20,200] [--n_envs 128]
       [--tpu_lock wait|fail|off]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from distributed_ba3c_tpu.utils.devicelock import guard_tpu, stderr_print  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n_envs", default="128",
                    help="comma list of per-chip env counts; multiple "
                    "values capture shard-shape rows (e.g. 8,16 = the "
                    "4-way / 2-way shards of the solving batch)")
    ap.add_argument("--rollout_len", type=int, default=20)
    ap.add_argument("--total", type=int, default=200,
                    help="updates per timed window (must be divisible by each K)")
    ap.add_argument("--ks", default="1,20,200")
    ap.add_argument("--tpu_lock", default="wait", choices=["wait", "fail", "off"])
    args = ap.parse_args()

    _lock = guard_tpu(  # noqa: F841 — held for process lifetime
        "ksweep_bench",
        mode=args.tpu_lock,
        timeout_s=float(os.environ.get("BA3C_TPU_LOCK_TIMEOUT", "1800")),
    )
    from distributed_ba3c_tpu.utils.backend import (
        configure_compile_cache,
        device_info,
    )

    configure_compile_cache()

    from bench import bench_fused

    shapes = [int(n) for n in args.n_envs.split(",")]
    ks = [int(k) for k in args.ks.split(",")]
    rows: dict[str, dict] = {}
    for n_envs in shapes:
        out: dict[int, float] = {}
        windows: dict[int, list[float]] = {}
        for K in ks:
            r = bench_fused(
                n_envs=n_envs, rollout_len=args.rollout_len,
                iters=args.total, steps_per_dispatch=K,
            )
            out[K] = r["value"]
            windows[K] = r["window_rates"]
            stderr_print(
                f"{n_envs}x{args.rollout_len} K={K}: {r['value']} "
                f"env-steps/s/chip  windows={r['window_rates']}"
            )
        rows[f"{n_envs}x{args.rollout_len}"] = {
            "per_chip_by_K": out, "windows_by_K": windows,
        }

    payload = {
        "metric": "fused_pong_ksweep_env_steps_per_sec_per_chip",
        "shape": ",".join(rows),
        "total_updates_per_window": args.total,
        "rows": rows,
        "device": device_info(),
    }
    if len(shapes) == 1:
        # legacy single-shape schema (runs/ksweep_r5.json, test_bench.py)
        payload.update(next(iter(rows.values())))
    print(json.dumps(payload))


if __name__ == "__main__":
    main()
