"""The layer scopes of a token-sequence policy, read from the run's capture.

``utils/profiling.py`` opens ``embed`` .. ``head`` (``POLICY_LAYERS``) under
``rollout/policy`` (the decode step) and under ``learner`` (the unroll)
alike. ``seconds(ctx, "MOE_EXPERTS")`` is the device time under both;
``None`` where the capture cannot be read or the program has no such scope
(a program from before them): the metric then leaves itself out.
"""

from __future__ import annotations

from typing import Optional

from benchmark import scopes

UNDER = ("ROLLOUT_POLICY", "LEARNER")


def seconds(ctx, layer_attr: str, under=UNDER) -> Optional[float]:
    cap = scopes.capture(ctx)
    if cap is None:
        return None
    prof = cap["profiling"]
    try:
        names = [prof.policy_scope(getattr(prof, u), getattr(prof, layer_attr))
                 for u in under]
        return sum(cap["seconds"][n] for n in names)
    except (AttributeError, KeyError):
        return None


def share(ctx, *layer_attrs: str, under=UNDER) -> Optional[float]:
    """Percent of the device's op time under the named layer scopes."""
    parts = [seconds(ctx, a, under) for a in layer_attrs]
    if any(p is None for p in parts):
        return None
    return 100.0 * sum(parts) / scopes.capture(ctx)["total_s"]


def line(ctx, *layer_attrs: str) -> str:
    """``moe/experts rollout 1.2 % learner 3.4 %`` of each named scope."""
    prof = scopes.capture(ctx)["profiling"]
    return ", ".join(
        f"{getattr(prof, a)} rollout {share(ctx, a, under=UNDER[:1]):.3f} % "
        f"learner {share(ctx, a, under=UNDER[1:]):.3f} %"
        for a in layer_attrs)


def updates(ctx) -> float:
    """Executions of the compiled update in the traced window, a chip."""
    return ctx["trace"].module_runs(ctx["config"]["trace"]["update_module"])


def visits_per_update(ctx) -> Optional[float]:
    """(token, held expert) visits one update's learner made, all layers."""
    held = ctx["counters"].get("moe_tokens_per_expert")
    if held is None:
        return None
    return float(sum(sum(layer) for layer in held))
