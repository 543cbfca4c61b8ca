"""Time a chip spends between two updates: the median, over the chips, from
the end of one execution of the compiled step (the configuration's
``trace.update_module``) to the start of the next: the idle share in its
per-update form. Printed beside it: the program's host span (``fused.*``)
that covers most of the gaps' time, and the programs that ran inside them."""

from benchmark import scopes

ROW = {
    "name": "interstep_gap_ms", "unit": "ms", "better": "lower",
    "source": "device_trace", "layer": "fused trainer",
    "moves": "env_steps_per_s_per_chip",
}


def read(ctx):
    cap = scopes.capture(ctx)
    if cap is None:
        return None
    tr, update = ctx["trace"], ctx["config"]["trace"]["update_module"]
    gaps, inside = [], {}
    for rows in tr.modules.values():
        rows = sorted(rows, key=lambda r: r[1])
        steps = [r for r in rows if update in r[0]]
        for a, b in zip(steps, steps[1:]):
            lo, hi = a[1] + a[2], b[1]
            gaps.append((lo, hi))
            for name, start, dur in rows:
                if lo <= start and start + dur <= hi:
                    short = name.split("(", 1)[0]
                    inside[short] = inside.get(short, 0) + 1
    if not gaps:
        return None
    covered = {}
    for lo, hi in gaps:
        for name, start, dur in cap["host_spans"]:
            c = min(hi, start + dur) - max(lo, start)
            if c > 0:
                covered[name] = covered.get(name, 0) + c
    total = sum(hi - lo for lo, hi in gaps)
    spans = ", ".join(
        f"{name} {100.0 * c / total:.1f} %"
        for name, c in sorted(covered.items(), key=lambda kv: -kv[1])[:3])
    programs = ", ".join(
        f"{name} x{n / len(gaps):.2f}" for name, n in sorted(inside.items()))
    print(f"interstep_gap_ms: {len(gaps)} gaps; of their time under the "
          f"program's spans: {spans or 'none'}; programs inside a gap: "
          f"{programs or 'none'}")
    lengths = sorted(hi - lo for lo, hi in gaps)
    return lengths[len(lengths) // 2] / 1e6
