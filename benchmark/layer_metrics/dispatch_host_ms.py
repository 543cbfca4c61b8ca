"""Host time of one dispatch: the median ``fused.step`` span of the program
(``fused/loop.py``'s ``step()``), on the capture's clock; the medians of its
two parts, ``fused.step.hyper`` and ``fused.step.enqueue``, are printed."""

from benchmark import scopes

ROW = {
    "name": "dispatch_host_ms", "unit": "ms", "better": "lower",
    "source": "program_span", "layer": "fused trainer",
    "moves": "env_steps_per_s_per_chip",
}


def _median_ms(cap, span):
    durs = sorted(d for name, _start, d in cap["host_spans"] if name == span)
    return durs[len(durs) // 2] / 1e6 if durs else None


def read(ctx):
    cap = scopes.capture(ctx)
    if cap is None:
        return None
    prof = cap["profiling"]
    parts = ", ".join(
        f"{span} {ms:.3f} ms" for span in (prof.SPAN_STEP_HYPER, prof.SPAN_STEP_ENQUEUE)
        if (ms := _median_ms(cap, span)) is not None)
    print(f"dispatch_host_ms: of which {parts}")
    return _median_ms(cap, prof.SPAN_STEP)
