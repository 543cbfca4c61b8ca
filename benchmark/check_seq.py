"""``correct`` for a dense token-sequence policy that carries state:
``benchmark/check.py``'s five numbers, the loss on an absolute floor, and
one more.

- ``logit_gap``: the program's DECODE, token by token through the policy's
  carry (the state-space state and conv tail, the ring of the last
  ``window`` keys and values, the K/V one layer writes and others read), at
  the weights the run starts from, over the first ``decode_check_envs``
  episodes the followed update played, against the reference's forward over
  the same tokens (whole episodes at once: no cache, no ring). The largest
  gap between the two sides' logits over the reference's largest logit,
  over every position, those past the window included. A ring that forgets
  too early or too late, a reset that leaves state behind, a reader of the
  wrong layer's K/V, all land here; rounding alone reads small. Logits, not
  sampled tokens: with seeded weights the largest logit changes on rounding.
- ``loss_gap``: ``check.py`` divides the gap by the reference's loss, which
  for a near-uniform policy with a critic that starts near zero is a
  difference of small terms (PERF.md section 7: relative to a loss of 0.001
  it read 0.0108 on a sound run). Here the gap is over the larger of the
  reference's loss and :data:`LOSS_FLOOR`, the entropy term a uniform
  policy over the held ids gives at the configuration's beta: the size of
  the loss's parts, whatever their sum.
- the two norm gaps are ``check.py``'s, by the worst leaf; their printed
  lines also give the median leaf and the worst leaf that is not a ``lam``
  vector. A ``lam`` vector's gradient is one scalar (the loss's slope in
  ``lam``, summed over every token and pair) times a fixed vector, so the
  gap of its norm IS that scalar's relative error; a matrix's norm averages
  its entries' rounding away (PERF.md section 4 has both measured). The
  worst leaf of a sound run is nearly always such a vector, at a size that
  swings with the seed; the other two readings say what the rest did.
"""

from __future__ import annotations

import math
import statistics
from typing import Dict, List, Sequence

import numpy as np

from benchmark import check

NUMBERS = ("logit_gap",)


def loss_floor(entropy_beta: float, ids: int) -> float:
    return entropy_beta * math.log(ids)


def loss_gap(program: Sequence[float], reference: Sequence[float],
             floor: float) -> float:
    gaps = [abs(p - r) / max(abs(r), floor)
            for p, r in zip(program, reference, strict=True)]
    return float(max(gaps)) if all(np.isfinite(gaps)) else float("inf")


def leaf_gaps(program: Dict[str, float], reference: Dict[str, float]) -> Dict[str, float]:
    """Every leaf's gap as ``check.worst_leaf_gap`` reckons its worst."""
    floor = statistics.median(reference.values())
    return {leaf: abs(program[leaf] - ref) / max(ref, floor, 1e-30)
            for leaf, ref in reference.items()}


def beside_the_worst(program: Dict[str, float], reference: Dict[str, float]) -> str:
    gaps = leaf_gaps(program, reference)
    rest = {leaf: g for leaf, g in gaps.items() if "/lam_" not in leaf}
    leaf = max(rest, key=rest.get)
    return (f"; median leaf {statistics.median(gaps.values()):.5g}; worst leaf "
            f"that is no lam vector {rest[leaf]:.5g} ({leaf})")


def compare(program: dict, reference: dict, limits: Dict[str, float],
            limits_seq: Dict[str, float], floor: float) -> List[dict]:
    """``program``/``reference``: what ``check.compare`` takes and
    ``decode_logits`` [envs, T, ids]. Rows as ``check.compare``'s."""
    rows = check.compare(program, reference, limits)
    for row in rows:
        if row["number"] == "loss_gap":
            row["value"] = loss_gap(program["losses"], reference["losses"], floor)
            row["detail"] += f" floor {floor:.5g}"
            row["ok"] = bool(row["value"] <= row["limit"])
        elif row["number"] == "first_grad_norm_gap":
            row["detail"] += beside_the_worst(
                program["first_grad"], reference["first_grad"])
        elif row["number"] == "param_delta_norm_gap":
            row["detail"] += beside_the_worst(program["delta"], reference["delta"])
    ref = np.asarray(reference["decode_logits"], np.float32)
    gap = np.abs(np.asarray(program["decode_logits"], np.float32) - ref).max(axis=-1)
    scale = float(np.abs(ref).max())
    worst = float(gap.max()) / scale if np.isfinite(gap).all() else float("inf")
    at = tuple(int(i) for i in np.unravel_index(gap.argmax(), gap.shape))
    rows.append({
        "number": "logit_gap", "value": worst, "limit": limits_seq["logit_gap"],
        "ok": bool(worst <= limits_seq["logit_gap"]),
        "detail": f"median token {float(np.median(gap)) / scale:.5g}; largest "
                  f"reference logit {scale:.5g}; worst token (env, position) {at}; "
                  f"worst by quarter of the episode "
                  + " ".join(f"{float(q.max()) / scale:.5g}"
                             for q in np.array_split(gap, 4, axis=1)),
    })
    return rows
