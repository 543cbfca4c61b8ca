"""The policy protocol and the registry of policies by name (ROADMAP A0).

A policy is what the trainers call to turn observations into
:class:`PolicyValue`. Two kinds exist:

- a **stateless** policy (``BA3CNet``): a flax module;
  ``model.apply({"params": p}, obs) -> PolicyValue`` over a batch of
  independent observations. Its carry is the empty pytree ``()``.
- a policy that **carries state** (``carries_state = True``): besides its
  parameters it gives

      model.init_params(rng) -> params           {layer: {leaf: array}}
      model.init_carry(batch) -> carry           pytree, leaves [batch, ...]
      model.step(params, obs, carry, fresh) -> (PolicyValue, carry, aux)
          one observation an env; where ``fresh`` (bool [batch]) the
          observation opens an episode and the carry is reset before use
      model.unroll(params, obs_seq) -> (PolicyValue, aux)
          whole episodes ``[batch, T]`` from a reset, causal over ``T``;
          the learner's forward
      model.rollout_params(params) -> params     what ``step`` is served
          from all through one rollout (a bfloat16 snapshot of the matrices)

  ``aux`` is a dict of whatever the policy counts in its learner (summed
  over chunks and shards into the step's metrics; may be empty); ``step``
  and ``unroll`` agree position by position (each policy's own test file,
  tests/test_<module>.py). Under the one reserved
  key :data:`LOSS_TERMS` the unroll's ``aux`` may hold **loss terms the
  policy owns**: ``{name: array}``, each entry a mean over the chunk's
  tokens (a scalar, or one a layer) with its coefficient applied. The
  trainer adds the sum of them to ``a3c_loss.total`` before it
  differentiates, averages them over chunks and shards as it averages the
  loss's parts, and reports each under the policy's name for it; it names
  none of them (``keye-vl2``'s ``indexer_kl`` trains its indexer, which the
  A2C loss cannot reach). Optional, for the trainer's reports:

      model.carry_gauges(carry) -> dict          of the carry as a rollout left
          it (the largest over the shards goes into the step's metrics)
      model.epoch_stats(metrics) -> dict         an epoch's scalars of the
          policy's own counters and gauges, for stat.json

Only the fused trainer drives a policy that carries state; every other
trainer refuses one through :func:`refuse_carry`. What the carrying
policies share beyond this protocol (a base class, the unroll's skeleton,
the decode step's common parts) is models/sequence.py's; what a new one
writes is in docs/policy_protocol.md, "Adding a policy".
"""

from __future__ import annotations

import importlib
from typing import Dict, Tuple

import jax.numpy as jnp

DEFAULT_MODEL = "ba3cnet"
#: the key of an unroll's ``aux`` that holds the policy's own loss terms
LOSS_TERMS = "loss_terms"


def carries_state(model) -> bool:
    return bool(getattr(model, "carries_state", False))


def refuse_carry(model, what: str) -> None:
    """The one error of every path that cannot drive a policy's carry."""
    if carries_state(model):
        raise ValueError(
            f"{what} cannot drive a policy that carries state "
            f"({type(model).__name__}): only --trainer tpu_fused_ba3c "
            "(without --overlap) threads a policy carry through its rollout"
        )


def init_params(model, rng, cfg):
    """Seeded parameters of either kind of policy."""
    if carries_state(model):
        return model.init_params(rng)
    dummy = jnp.zeros((1, *cfg.state_shape), jnp.uint8)
    return model.init(rng, dummy)["params"]


#: name -> (module of this package, class): the policies ``--model`` names.
#: A module is imported when its policy is built or described and not
#: before (a ``ba3cnet`` run imports no Pallas). A policy that carries state
#: is models/sequence.py's: its module has ``CUTS`` and ``cut_fields``.
MODELS: Dict[str, Tuple[str, str]] = {
    DEFAULT_MODEL: ("a3c", "BA3CNet"),
    "lfm2-moe": ("lfm2_moe", "LFM2MoE"),
    "phi4-flash": ("phi4_flash", "Phi4Flash"),
    "keye-vl2": ("keye_vl2", "KeyeVL2"),
    "olmo-hybrid": ("olmo_hybrid", "OlmoHybrid"),
    "nemotron-h": ("nemotron_h", "NemotronH"),
    "xing4": ("xing4", "Xing4"),
}


def _module_of(name: str):
    return importlib.import_module(f"{__package__}.{MODELS[name][0]}")


def build_model(name: str, cfg, cut: str | None = None):
    """The policy ``--model name`` names, its action space from ``cfg``; of
    a policy that carries state, the share of it ``--model_cut cut`` names."""
    if name not in MODELS:
        raise ValueError(f"unknown model {name!r}; have {sorted(MODELS)}")
    module = _module_of(name)
    model = getattr(module, MODELS[name][1])
    if not carries_state(model):
        if cut is not None:
            raise ValueError(f"{name} has no --model_cut")
        return model(num_actions=cfg.num_actions, fc_units=cfg.fc_units)
    return model(num_actions=cfg.num_actions, **module.cut_fields(cut))


def cuts_by_model() -> Dict[str, Tuple[str, ...]]:
    """Every carrying policy's ``--model_cut`` names, its default first
    (``cli.py``'s help: this imports every policy's module)."""
    return {name: tuple(_module_of(name).CUTS)
            for name in MODELS if name != DEFAULT_MODEL}
