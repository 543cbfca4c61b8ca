"""Process start-up against the JAX back-end: compile cache, device report,
and the record of what start-up spent its time on.

Every entry point that compiles for the chip calls
:func:`configure_compile_cache` before its first jit and
:func:`log_device_info` once after it, so that no run starts from an empty
cache by accident and no number is ever printed without the device it came
from (docs/OPERATIONS.md "Running on the chip").

:func:`configure_compile_cache` also installs the **start-up record**
(:func:`install_startup_record`): listeners on ``jax.monitoring`` that keep
every trace, lowering and compile-or-cache-read of the process as an interval
under its function's name, on ``time.monotonic()``, beside the count of fused
``step()`` calls at that moment (``profiling.step_calls()``) and the
``startup`` events the program records itself (the step's first calls,
``run_fused_training``'s one-off phases). :func:`startup_summary` reduces the
record; ``run_fused_training`` logs it as one line at its first update
(:func:`report_startup`) and the benchmark's ``setup_*`` metrics read it
(``benchmark/startup.py``). Counters and events go to ``telemetry``
(docs/observability.md), and ``BA3C_TELEMETRY=0`` turns every listener into
a branch-and-return, as it does every other series.
"""

from __future__ import annotations

import contextlib
import os
import re
import threading
import time
from typing import Dict, List, NamedTuple, Optional

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

#: the in-checkout cache directory (git-ignored). FIXED on purpose: the
#: path is part of the cache key, so a directory under tempfile, a pid or a
#: timestamp never hits.
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def cpu_only() -> bool:
    """True when ``JAX_PLATFORMS`` restricts this process to the CPU (the
    sealed chip machine exports ``tpu,cpu``; unset lets JAX take the best
    back-end present)."""
    plats = [
        p.strip()
        for p in os.environ.get("JAX_PLATFORMS", "").split(",")
        if p.strip()
    ]
    return bool(plats) and all(p == "cpu" for p in plats)


def configure_compile_cache() -> Optional[str]:
    """Place JAX's persistent compilation cache; returns the directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set in code (an operator or the chip tool owns the place).
    Otherwise the cache goes to :data:`REPO_CACHE_DIR` — except in a
    CPU-only process, which gets none (returns None): the cache exists for
    chip compiles, and this jaxlib's CPU client logs a machine-feature
    mismatch error on every cached executable it loads. Child processes
    that compile for a chip call this too and resolve the same directory.
    Wherever a cache is in use its key takes in the program's metadata.
    In every process the start-up record's listeners are installed first.
    """
    install_startup_record()  # first: a CPU-only process gets it too
    placed = os.environ.get(CACHE_DIR_ENV)
    if not placed and cpu_only():
        return None
    import jax

    # the default key ignores op names and source lines, so a program read
    # from the cache keeps those it was first compiled with: a capture
    # would show an older build's scopes (utils/profiling.py), or none
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR


def device_info() -> Dict[str, object]:
    """The device as JAX reports it (initialises the back-end)."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def log_device_info() -> Dict[str, object]:
    """Log the device and the cache directory once; returns the device."""
    import jax

    from distributed_ba3c_tpu.utils import logger

    info = device_info()
    logger.info(
        "device: platform=%s kind=%s count=%d (jax %s, compile cache %s)",
        info["platform"], info["kind"], info["count"], jax.__version__,
        os.environ.get(CACHE_DIR_ENV) or jax.config.jax_compilation_cache_dir,
    )
    return info


# -- the start-up record ------------------------------------------------------
#: the stage of an interval, by the ``jax.monitoring`` time span that ends it.
#: ``compile_load`` wraps ``compile_or_get_cached``: a compilation OR a cache
#: read, up to a loaded executable; the interval's ``cache`` says which
STAGE_OF_EVENT = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile_load",
}
TRACE, LOWER, COMPILE_LOAD = STAGE_OF_EVENT.values()
CACHE_OF_EVENT = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
CACHE_READ_S_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
TIME_SAVED_S_EVENT = "/jax/compilation_cache/compile_time_saved_sec"
#: intervals kept; one past that is counted in ``dropped`` and not kept (a
#: union over the kept ones is then a lower bound, and every summary says
#: so). A benchmark run leaves 6,355 in a conv cell and 31,279 in the
#: sparse-attention cell (on the v5e, PR 38): a trace event fires for every
#: jitted ``jax.numpy`` function inside a traced one. About 15 MB when full
MAX_INTERVALS = 65536
#: an interval this long is also a flight-recorder ``compile`` event, and
#: after warm-up a warning: the small programs stay out of ring and log
EVENT_FROM_S = 0.010
STEP_EVENT = "fused.step#"  # + k: the step's k-th call (fused/loop.py)


class Interval(NamedTuple):
    stage: str
    fun_name: str
    start: float  # time.monotonic()
    end: float
    step_calls: int  # profiling.step_calls() when the interval ended
    #: of a compile_load: hit; miss (compiled and written to the cache); none
    #: (no cache asked, or a program under the cache's thresholds)
    cache: str = "none"
    cache_read_s: float = 0.0  # JAX's own two durations, of a hit
    time_saved_s: float = 0.0


class StartupEvent(NamedTuple):
    name: str
    start: float
    end: float
    parts: Dict[str, float]  # seconds of the event's named parts


class StartupRecord:
    """What one process's listeners heard. Append-only; a list's append is
    atomic under the GIL (a compilation may run on any thread), the count of
    drops is not and has a lock, taken on no path a kept interval takes."""

    def __init__(self, process_start: Optional[float], installed: float):
        #: when the process began, as the OS has it, on ``time.monotonic()``;
        #: None where that could not be read (``installed`` stands in)
        self.process_start = process_start
        self.installed = installed  # imports done up to the installer's call
        self.intervals: List[Interval] = []
        self.dropped = 0
        self.drop_lock = threading.Lock()
        self.events: List[StartupEvent] = []
        #: names that have a compile_load interval (kept or dropped); once
        #: ``warm`` (set by report_startup) one of them compiling AGAIN is news
        self.compiled: set = set()
        self.warm = False

    @property
    def origin(self) -> float:
        """Where the summary counts from: the OS's stamp, else the installer's."""
        return (self.installed if self.process_start is None
                else self.process_start)


_record: Optional[StartupRecord] = None
_pending = threading.local()  # a compile_load's cache outcome, till it ends
_telemetry = None  # the telemetry package, imported by the installer
_profiling = None


def _os_process_start() -> Optional[float]:
    """The process's start on ``time.monotonic()``'s scale, from its start
    time in ``/proc/self/stat`` (field 22, clock ticks since boot) against
    ``CLOCK_BOOTTIME``, the two clocks read one after the other; None off
    Linux or where the two do not agree."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        started = ticks / os.sysconf("SC_CLK_TCK")
        age = time.clock_gettime(time.CLOCK_BOOTTIME) - started
        now = time.monotonic()
    except (OSError, ValueError, IndexError, AttributeError):
        return None
    return now - age if 0.0 <= age < 7 * 86400.0 else None


def install_startup_record() -> StartupRecord:
    """Register the process's listeners on ``jax.monitoring``, once; -> the
    record. Idempotent: a second call registers nothing."""
    global _record, _telemetry, _profiling
    if _record is not None:
        return _record
    from jax import monitoring

    from distributed_ba3c_tpu import telemetry
    from distributed_ba3c_tpu.utils import profiling

    _telemetry, _profiling = telemetry, profiling
    # both stamps after the imports above, which are start-up's too
    _record = StartupRecord(_os_process_start(), time.monotonic())
    monitoring.register_event_time_span_listener(_on_time_span)
    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)
    return _record


def startup_record() -> Optional[StartupRecord]:
    return _record


def _on_event(event: str, **_kw) -> None:
    cache = CACHE_OF_EVENT.get(event)
    if cache is None or not _telemetry.enabled():
        return
    _pending.cache = cache
    tele = _telemetry.registry("learner")
    if cache == "hit":
        tele.counter("compile_cache_hits_total").inc()
    else:
        tele.counter("compile_cache_misses_total").inc()


def _on_duration(event: str, seconds: float, **_kw) -> None:
    if (event not in (CACHE_READ_S_EVENT, TIME_SAVED_S_EVENT)
            or not _telemetry.enabled()):
        return
    tele = _telemetry.registry("learner")
    if event == CACHE_READ_S_EVENT:
        _pending.cache_read_s = seconds
        tele.counter("compile_cache_read_s_total").inc(seconds)
    else:
        _pending.time_saved_s = seconds
        tele.counter("compile_time_saved_s_total").inc(seconds)


def _on_time_span(event: str, start_time: float, end_time: float,
                  fun_name: str = "", **_kw) -> None:
    stage = STAGE_OF_EVENT.get(event)
    if stage is None or not _telemetry.enabled():
        return
    # JAX stamps time.time(), which jumps: keep its length, on our clock
    end = time.monotonic()
    dur = end_time - start_time
    outcome = {}
    if stage == COMPILE_LOAD:
        outcome = dict(vars(_pending))
        vars(_pending).clear()
    interval = Interval(stage, str(fun_name), end - dur, end,
                        _profiling.step_calls(), **outcome)
    record = _record
    if len(record.intervals) < MAX_INTERVALS:
        record.intervals.append(interval)
    else:
        with record.drop_lock:
            record.dropped += 1
    tele = _telemetry.registry("learner")
    again = False
    if stage == TRACE:
        tele.counter("jit_traces_total").inc()
    elif stage == COMPILE_LOAD:
        tele.counter("backend_compiles_total").inc()
        # a function's FIRST program after warm-up is no recompile (the
        # evaluator's, at the end of the first epoch); its second is
        again = record.warm and interval.fun_name in record.compiled
        record.compiled.add(interval.fun_name)
        if again:
            tele.counter("compiles_after_warmup_total").inc()
    if dur < EVENT_FROM_S:
        return
    _telemetry.record("compile", stage=stage, fun_name=interval.fun_name,
                      dur_s=dur, cache=interval.cache)
    if again:
        from distributed_ba3c_tpu.utils import logger

        logger.warn(
            "compiled again after warm-up: %s took %.2f s (cache %s) at step "
            "call %d", interval.fun_name, dur, interval.cache,
            interval.step_calls)
        _telemetry.record("retrace", entry=interval.fun_name, dur_s=dur,
                          cache=interval.cache, step_calls=interval.step_calls)


def startup_event(name: str, start: float, end: float, **parts: float) -> None:
    """One ``startup`` event of the program's own (a step's first call, a
    one-off phase): kept in the record and in the flight recorder."""
    if _record is None or not _telemetry.enabled():
        return
    _record.events.append(StartupEvent(name, start, end, parts))
    _telemetry.record("startup", name=name, dur_s=end - start, **parts)


@contextlib.contextmanager
def startup_phase(name: str):
    """Time one one-off phase of start-up as a ``startup`` event; the flight
    recorder hears of its beginning too, so the dump of a start-up that hung
    names the phase that began and did not end. The start-up line prints the
    phases (:func:`report_startup`)."""
    start = time.monotonic()
    if _record is not None and _telemetry.enabled():
        _telemetry.record("startup", name=name, at="begin")
    try:
        yield
    finally:
        startup_event(name, start, time.monotonic())


def _outermost(intervals) -> List[Interval]:
    """The intervals that lie inside no other, in start order: a jit traced
    inside another lies inside that one's trace."""
    out, reach = [], float("-inf")
    for i in sorted(intervals, key=lambda i: (i.start, -i.end)):
        if i.end > reach:
            out.append(i)
            reach = i.end
    return out


def _union_s(intervals) -> float:
    """Seconds covered by the intervals: one inside another counts once."""
    total, reach = 0.0, float("-inf")
    for i in _outermost(intervals):
        total += i.end - max(i.start, reach)
        reach = i.end
    return total


_WRAPPED = re.compile(r"^(?:jit|pmap)(?:\((.*)\)|_(.*))$")


def _bare(fun_name: str) -> str:
    """``multi_step`` of ``jit(multi_step)``: tracing names the function, the
    later stages the module made of it."""
    found = _WRAPPED.match(fun_name)
    return (found.group(1) or found.group(2)) if found else fun_name


def startup_summary(before_step_calls: Optional[int] = None,
                    record: Optional[StartupRecord] = None) -> Optional[dict]:
    """The record reduced; None where no record was installed.

    ``before_step_calls``: keep the intervals recorded while
    ``profiling.step_calls()`` was below this count (a benchmark run passes
    the final count once its window is over: what was compiled after the last
    dispatch falls out, and a sound window compiles nothing); None keeps all.

    -> ``until_first_trace_s`` (process start to the first ``trace``
    interval: interpreter, imports, chip init, building the step; None with
    no trace yet), of which ``installed_s`` up to the installer's call;
    ``process_start_from`` (``os`` or ``installer``: the latter where the
    OS's stamp could not be read, and the two numbers then leave out what
    came before the installer); ``trace_lower_s`` and ``compile_load_s`` as
    UNIONS of intervals (a jit traced inside another is not counted twice);
    ``cache_hits``, ``cache_misses``, ``cache_read_s``, ``time_saved_s`` of
    the kept ``compile_load`` intervals; ``missed``: [fun_name, seconds] of
    each miss; ``dropped`` (above 0 the unions are lower bounds);
    ``costliest``: the ten functions with most seconds, [name, {stage:
    seconds}] (a function's seconds include those of the jits inside it);
    ``step_calls``: the recorded ``fused.step#k`` events, each with its
    ``parts`` and ``inside``: the outermost intervals that overlap it,
    [stage, fun_name, seconds, cache], and ``inside_s``, their union;
    ``phases``: the other ``startup`` events, [name, seconds];
    ``intervals``: how many the record holds."""
    record = record or _record
    if record is None:
        return None
    kept = [i for i in record.intervals
            if before_step_calls is None or i.step_calls < before_step_calls]
    origin = record.origin
    traces = [i for i in kept if i.stage == TRACE]
    loads = [i for i in kept if i.stage == COMPILE_LOAD]
    by_fun: Dict[str, Dict[str, float]] = {}
    for i in kept:
        stages = by_fun.setdefault(_bare(i.fun_name), {})
        stages[i.stage] = stages.get(i.stage, 0.0) + i.end - i.start
    steps, phases = [], []
    for ev in record.events:
        if not ev.name.startswith(STEP_EVENT):
            phases.append([ev.name, ev.end - ev.start])
            continue
        inside = [i for i in record.intervals
                  if i.end > ev.start and i.start < ev.end]
        steps.append({
            "name": ev.name, "step_s": ev.end - ev.start, "parts": ev.parts,
            "inside": [[i.stage, i.fun_name, i.end - i.start, i.cache]
                       for i in _outermost(inside)],
            "inside_s": _union_s(inside),
        })
    return {
        "process_start_from":
            "os" if record.process_start is not None else "installer",
        "installed_s": record.installed - origin,
        "until_first_trace_s":
            min(i.start for i in traces) - origin if traces else None,
        "trace_lower_s": _union_s(i for i in kept if i.stage != COMPILE_LOAD),
        "compile_load_s": _union_s(loads),
        "cache_hits": sum(i.cache == "hit" for i in loads),
        "cache_misses": sum(i.cache == "miss" for i in loads),
        "cache_read_s": sum(i.cache_read_s for i in loads),
        "time_saved_s": sum(i.time_saved_s for i in loads),
        "missed": [[i.fun_name, i.end - i.start]
                   for i in loads if i.cache == "miss"],
        "intervals": len(record.intervals),
        "dropped": record.dropped,
        "costliest": sorted(
            by_fun.items(), key=lambda kv: -sum(kv[1].values()))[:10],
        "step_calls": steps,
        "phases": phases,
    }


def costliest_line(summary: dict, stages=tuple(STAGE_OF_EVENT.values())) -> str:
    """``multi_step trace 3.55 lower 2.05, ...`` of a summary's costliest
    functions: those with time in ``stages``, most of it first."""
    rows = [(name, [(stage, by_stage[stage]) for stage in stages
                    if stage in by_stage])
            for name, by_stage in summary["costliest"]]
    rows.sort(key=lambda row: -sum(s for _, s in row[1]))
    return ", ".join(
        name + " " + " ".join(f"{stage} {s:.2f}" for stage, s in parts)
        for name, parts in rows if parts)


def report_startup(first_update_done: float) -> Optional[dict]:
    """The operator's reader, called once, when the first update is complete
    (``first_update_done`` on ``time.monotonic()``): logs the start-up line,
    with the one-off phases, sets the ``startup_*`` gauges of the ``learner``
    registry, and marks the record warm: from here on a ``compile_load``
    interval of a function that already has one (a recompile; a function's
    first program is none) is counted in ``compiles_after_warmup_total`` and,
    from :data:`EVENT_FROM_S`, warned of and recorded as a ``retrace`` event.
    -> the summary."""
    from distributed_ba3c_tpu.utils import logger

    summary = startup_summary()
    if summary is None or not _telemetry.enabled():
        return None
    record = _record
    total = first_update_done - record.origin
    until = summary["until_first_trace_s"]
    line = (
        f"start-up: {total:.1f} s to the first update: "
        f"{'no trace' if until is None else f'{until:.1f} until the first trace'}, "
        f"{summary['trace_lower_s']:.1f} trace+lower, "
        f"{summary['compile_load_s']:.1f} compile/load "
        f"({summary['cache_hits']} cache hits, {summary['cache_misses']} misses"
        + "".join(f": {name} {s:.1f} s" for name, s in summary["missed"][:3])
        + ")")
    first = next(
        (ev for ev in record.events if ev.name == f"{STEP_EVENT}1"), None)
    named = (until or 0.0) + summary["trace_lower_s"] + summary["compile_load_s"]
    if first is not None:
        line += f", first execution {first_update_done - first.end:.1f}"
        named += first_update_done - first.end
    line += f", {total - named:.1f} other host work"
    if summary["phases"]:
        # the same seconds cut the other way, by what the loop was doing (the
        # phases follow one another): the host work that no stage names lies
        # in one of them or, imports and the loop's other set-up, in none
        line += "; by phase: " + ", ".join(
            f"{name} {s:.1f}" for name, s in summary["phases"])
        line += f", {total - sum(s for _, s in summary['phases']):.1f} in none"
    if summary["process_start_from"] != "os":
        line += "; counted from the installer's call, not the process's start"
    if summary["dropped"]:
        line += f"; {summary['dropped']} intervals dropped: lower bounds"
    logger.info("%s", line)
    tele = _telemetry.registry("learner")
    tele.gauge("startup_s").set(total)
    tele.gauge("startup_until_first_trace_s").set(until or 0.0)
    tele.gauge("startup_trace_lower_s").set(summary["trace_lower_s"])
    tele.gauge("startup_compile_load_s").set(summary["compile_load_s"])
    tele.counter("compiles_after_warmup_total")  # a healthy run exports its 0
    record.warm = True
    return summary
