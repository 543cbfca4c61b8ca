"""``correct`` for a token-sequence policy with routed experts and attention
over the keys an indexer selects: ``benchmark/check_seq.py``'s six numbers
(``check.py``'s five with the loss on an absolute floor, and ``logit_gap``
of the DECODE through the carry) and two more.

The policy makes two discrete choices a token a layer, and each has
near-ties that flip between precisions: the top 8 of 128 router scores, and
the top 2,048 of up to 4,096 indexer scores. A flipped choice is a
different function of the weights. So in the learner **the reference
computes with the routes and with the selections the program's learner
used** (a separate compiled unroll hands them over) and reports what it
would have chosen itself; the loss (the differentiated total: A2C + the
indexer's KL), the gradient and the parameters' change then compare as
rounding alone, and the choices are held by their own numbers:

- ``route_flip_share``: the share of (token, layer) pairs whose set of
  chosen experts differs between the two sides;
- ``select_flip_share``: of the (query, key) pairs either side selected at
  the queries past position top-k - 1 (before it both keep every key),
  counted on each side, the share only one side selected: ``|A xor B| /
  (|A| + |B|)``. A program that selects half as many keys reads 1/3.

``logit_gap`` is NOT forced: the program's decode, token by token through
its caches with its own selection from its own bfloat16 scores, against the
reference's forward with its OWN selection and routes. A decode that
selects wrongly (a stale row, a wrong top-k, rows read past the position)
lands there; so do the flips' consequences, which is why its limit is the
widest.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from benchmark import check_seq

NUMBERS = ("logit_gap", "route_flip_share", "select_flip_share")


def select_flip_share(own, forced) -> float:
    """``|A xor B| / (|A| + |B|)`` of two selections (bool arrays of one
    shape), by hand on the host: what the reference counts on the device."""
    own, forced = np.asarray(own, bool), np.asarray(forced, bool)
    return float((own ^ forced).sum()) / max(float(own.sum() + forced.sum()), 1.0)


def compare(program: dict, reference: dict, limits: Dict[str, float],
            limits_sparse: Dict[str, float], floor: float) -> List[dict]:
    """``program``/``reference``: what ``check_seq.compare`` takes; the
    reference, having learned with the program's choices, also
    ``route_flip_share`` and ``select_flip_share`` (with their shares a
    layer) and the two parts of its loss. Rows as ``check.compare``'s."""
    rows = check_seq.compare(
        program, reference, limits,
        {"logit_gap": limits_sparse["logit_gap"]}, floor)
    for row in rows:
        if row["number"] == "loss_gap" and "indexer_kl" in reference:
            row["detail"] += (
                f"; A2C part program {program.get('a2c_losses')} reference "
                f"{reference['a2c_losses']}; indexer KL a layer program "
                f"{program.get('indexer_kl')} reference {reference['indexer_kl']}")
    for number in ("route_flip_share", "select_flip_share"):
        by_layer = reference[number.replace("_flip_share", "_flips_by_layer")]
        rows.append({
            "number": number, "value": float(reference[number]),
            "limit": limits_sparse[number],
            "ok": bool(reference[number] <= limits_sparse[number]),
            "detail": "by layer " + " ".join(f"{x:.5f}" for x in by_layer),
        })
    return rows
