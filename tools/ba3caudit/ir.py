"""IR plumbing for ba3caudit: jaxpr walking, HLO alias parsing, cost metrics.

Everything here is mechanism; the invariants live in rules.py. The walkers
are deliberately structural — they descend into ANY eqn param that holds a
(Closed)Jaxpr (pjit bodies, scan/while bodies, cond branches, shard_map,
custom_vjp calls), so a collective or conv hiding three nesting levels deep
in the fused step is still seen.
"""

from __future__ import annotations

import re
from collections import Counter
from typing import Any, Dict, Iterator, List, Tuple

# All-reduce-sum as a jaxpr spells it. Under shard_map's check_vma typing
# (the only mode the step builders use) every cross-device sum — a user's
# lax.psum/pmean AND the reduction the transpose inserts for the gradient of
# a replicated input — is a ``psum_invariant`` eqn; plain ``psum`` appears
# only under check_vma=False.
PSUM_PRIMS = {"psum", "psum_invariant"}

# communicating collectives (primitive names as they appear in jaxprs)
COLLECTIVE_PRIMS = PSUM_PRIMS | {
    "pmin",
    "pmax",
    "ppermute",
    "pbroadcast",
    "all_gather",
    "all_to_all",
    "all_gather_invariant",
    "reduce_scatter",
    "psum_scatter",
}

# host-transfer / host-callback primitives: none may appear in a hot path
HOST_PRIMS = {
    "pure_callback",
    "io_callback",
    "debug_callback",
    "debug_print",
    "outside_call",
    "host_callback",
    "infeed",
    "outfeed",
}

CONV_PRIM = "conv_general_dilated"
DOT_PRIM = "dot_general"


def _subjaxprs(eqn) -> Iterator[Any]:
    for v in eqn.params.values():
        items = v if isinstance(v, (list, tuple)) else [v]
        for item in items:
            if hasattr(item, "eqns"):  # open Jaxpr
                yield item
            elif hasattr(item, "jaxpr") and hasattr(item.jaxpr, "eqns"):
                yield item.jaxpr  # ClosedJaxpr

def iter_eqns(jaxpr) -> Iterator[Any]:
    """Yield every eqn in ``jaxpr`` and, recursively, in all sub-jaxprs."""
    if hasattr(jaxpr, "jaxpr"):  # ClosedJaxpr -> Jaxpr
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _subjaxprs(eqn):
            yield from iter_eqns(sub)


def _in_avals(eqn) -> List[Any]:
    return [v.aval for v in eqn.invars if hasattr(v, "aval")]


def collective_census(jaxpr) -> Counter:
    """primitive name -> count, over every collective eqn in the program."""
    return Counter(
        e.primitive.name for e in iter_eqns(jaxpr)
        if e.primitive.name in COLLECTIVE_PRIMS
    )


def host_callback_census(jaxpr) -> Counter:
    return Counter(
        e.primitive.name for e in iter_eqns(jaxpr)
        if e.primitive.name in HOST_PRIMS
    )


def conv_operand_dtypes(jaxpr) -> List[Tuple[str, ...]]:
    """Per conv eqn: the tuple of operand dtype names (lhs, rhs)."""
    out = []
    for e in iter_eqns(jaxpr):
        if e.primitive.name == CONV_PRIM:
            out.append(tuple(str(a.dtype) for a in _in_avals(e)))
    return out


def dot_dtype_census(jaxpr) -> Counter:
    """dtype name of the lhs operand -> count, over every dot_general."""
    census: Counter = Counter()
    for e in iter_eqns(jaxpr):
        if e.primitive.name == DOT_PRIM:
            avals = _in_avals(e)
            if avals:
                census[str(avals[0].dtype)] += 1
    return census


def nonscalar_psum_shapes(jaxpr) -> List[Tuple[int, ...]]:
    """Operand shapes of every all-reduce-sum over a non-scalar array.

    The step's gradient all-reduce is one psum per param leaf (inserted by
    the shard_map transpose, see :data:`PSUM_PRIMS`); everything else the
    steps psum (metrics, episode counters) is scalar, so the non-scalar
    psum multiset IS the gradient-reduction census. (psum is variadic —
    one eqn may carry several operands.)
    """
    shapes: List[Tuple[int, ...]] = []
    for e in iter_eqns(jaxpr):
        if e.primitive.name in PSUM_PRIMS:
            for a in _in_avals(e):
                if getattr(a, "ndim", 0) >= 1:
                    shapes.append(tuple(a.shape))
    return shapes


# --------------------------------------------------------------------------
# compiled-module facts
# --------------------------------------------------------------------------

_ALIAS_MARKER = "input_output_alias={"
_ALIAS_ENTRY_RE = re.compile(r"\{[0-9, ]*\}:\s*\((\d+)\s*,")


def input_aliases(compiled_text: str) -> List[int]:
    """Parameter indices that alias an output, parsed from the compiled
    module header's ``input_output_alias={ {out}: (param, {}, may-alias) }``.

    XLA drops unusable donations silently at lowering (jax only warns), so
    the REQUESTED donation in the jaxpr proves nothing — this header is the
    materialized truth. The block nests braces (output indices, tuple
    paths), so it is extracted with a depth scan, not a regex.
    """
    start = compiled_text.find(_ALIAS_MARKER)
    if start < 0:
        return []
    i = start + len(_ALIAS_MARKER)
    depth = 1
    while i < len(compiled_text) and depth:
        depth += {"{": 1, "}": -1}.get(compiled_text[i], 0)
        i += 1
    block = compiled_text[start + len(_ALIAS_MARKER): i - 1]
    return sorted(int(g) for g in _ALIAS_ENTRY_RE.findall(block))


def cost_metrics(compiled) -> Dict[str, float]:
    """{'flops': ..., 'bytes_accessed': ...} from XLA's cost analysis."""
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
    }
