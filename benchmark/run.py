"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process that holds the cell's chips: it builds weights, env state and
inputs on the device from ``--seed``, warms up the cell's shapes through
JAX's persistent compile cache, measures for ``--seconds``, checks what the
timed program produced against the plain float32 reference, and prints one
JSON object as its last line. It fails on anything but a TPU that is in the
peaks table; it never falls back to the CPU.
"""

from __future__ import annotations

import time

_PROCESS_START = time.monotonic()  # before the heavy imports: they are set-up

import argparse
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import trace as trace_mod  # noqa: E402
from benchmark.spec import Benchmark, SpecError  # noqa: E402

TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class NoChip(Exception):
    """The machine does not hold the accelerator the cell asks for."""


def gate(devices, chips: int, bench: Benchmark) -> dict:
    """The cell's chips, or ``NoChip``; -> the contract's ``device``."""
    if not devices or devices[0].platform != "tpu":
        raise NoChip(
            f"platform {devices[0].platform if devices else None!r} is not a TPU"
        )
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devices)}")
    kind = devices[0].device_kind
    try:
        bench.peaks(kind)
    except SpecError as e:
        raise NoChip(str(e)) from e
    return {"platform": "tpu", "kind": kind, "count": chips}


def claim_chips(bench: Benchmark, cell: dict):
    """Place the compile cache, then the cell's chips: (devices, device).

    The cache goes where the program puts it: ``$JAX_COMPILATION_CACHE_DIR``,
    else the fixed ``<checkout>/.jax_cache``; every program is kept there,
    however small. Raises ``NoChip`` off a TPU."""
    import jax

    from distributed_ba3c_tpu.utils.backend import configure_compile_cache

    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    try:
        devices = jax.devices()
    except RuntimeError as e:  # JAX found no back-end it was told to use
        raise NoChip(str(e)) from e
    return devices, gate(devices, cell["chips"], bench)


class Tracer:
    """Profiles ``seconds`` of the window, from ``start_at`` seconds in."""

    def __init__(self, out_dir: str, start_at: float, seconds: float):
        self.out_dir, self.start_at, self.seconds = out_dir, start_at, seconds
        self.on_since = None
        self.done = False

    def tick(self, elapsed: float):
        import jax

        if self.done:
            return
        if self.on_since is None:
            if elapsed >= self.start_at:
                jax.profiler.start_trace(self.out_dir)
                self.on_since = elapsed
        elif elapsed - self.on_since >= self.seconds:
            self.close()

    def close(self):
        import jax

        if self.on_since is not None and not self.done:
            jax.profiler.stop_trace()
        self.done = True


class CompileCounter:
    """Counts back-end compilations (there should be none in the window)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _seconds: float, **_kw):
        if event == self.EVENT:
            self.count += 1


def measure(bench: Benchmark, cell: dict, config: dict, devices, device: dict,
            seed: int, seconds: float, traced: bool) -> dict:
    """Set-up, window and comparison of one cell on ``devices``."""
    driver = bench.driver(config["driver"])
    compiles = CompileCounter()
    chip_ready = time.monotonic() - _PROCESS_START
    session = driver.setup(cell, config, devices, seed)
    tracer = None
    out_dir = os.path.join(TRACE_DIR, cell["name"])
    if traced:
        shutil.rmtree(out_dir, ignore_errors=True)
        span = float(cell["trace_seconds"])
        tracer = Tracer(out_dir, max(0.0, (seconds - span) / 2), span)
    compiled_before = compiles.count
    setup_s = time.monotonic() - _PROCESS_START
    print(f"setup: {setup_s:.2f} s, of which imports and chip init "
          f"{chip_ready:.2f} s, the step's first call "
          f"{session.counters.get('first_dispatch_s', float('nan')):.2f} s")
    win = session.window(seconds, tracer)
    compiled_inside = compiles.count - compiled_before
    device["memory_peak_bytes"] = session.memory_peak_bytes()
    session.release()

    rows = session.check()
    for row in rows:
        print(
            f"compare {row['number']}: {row['value']:.6g} limit {row['limit']:.6g} "
            f"{'ok' if row['ok'] else 'FAIL'} ({row['detail']})"
        )
    failed = win["failed"] + compiled_inside
    print(f"window: {win['attempted']} updates attempted, {win['failed']} failed, "
          f"{compiled_inside} compilations inside the window")
    result = {
        "correct": all(r["ok"] for r in rows) and failed == 0,
        "attempted": win["attempted"], "failed": failed, "device": device,
    }

    if not traced:
        values = dict(win["end_to_end"], setup_s=setup_s)
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in bench.end_to_end()
        }
        return result

    tr = trace_mod.load(trace_mod.find_xplane(out_dir))
    device["busy_s"], device["window_s"] = tr.busy_s(), tr.window_s()
    ctx = {
        "trace": tr, "counters": session.counters, "cell": cell,
        "config": config, "peaks": bench.peaks(device["kind"]),
    }
    metrics = {}
    for entry in bench.per_layer(cell["name"]):
        value = bench.layer_metric(entry["name"]).read(ctx)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    result["metrics"] = metrics
    result["breakdown"] = {"device_ops": tr.top_ops(), "idle_gaps": tr.idle_gaps()}
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = p.parse_args(argv)

    bench = Benchmark()
    cell = bench.cell(args.workload)
    config = bench.config(cell["config"])

    try:
        devices, device = claim_chips(bench, cell)
    except NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    result = measure(
        bench, cell, config, devices, device, args.seed, args.seconds,
        bool(args.trace),
    )
    sys.stdout.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
