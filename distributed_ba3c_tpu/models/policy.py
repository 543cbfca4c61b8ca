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
  and ``unroll`` agree position by position (tests/test_lfm2_moe.py,
  tests/test_phi4_flash.py, tests/test_keye_vl2.py,
  tests/test_olmo_hybrid.py, tests/test_nemotron_h.py). Under the one reserved
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
trainer refuses one through :func:`refuse_carry`. docs/policy_protocol.md.
"""

from __future__ import annotations

from typing import Callable, Dict

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


def _ba3cnet(cfg, cut=None):
    from distributed_ba3c_tpu.models.a3c import BA3CNet

    if cut is not None:
        raise ValueError("ba3cnet has no --model_cut")
    return BA3CNet(num_actions=cfg.num_actions, fc_units=cfg.fc_units)


def _lfm2_moe(cfg, cut=None):
    from distributed_ba3c_tpu.models.lfm2_moe import LFM2MoE, cut_fields

    return LFM2MoE(num_actions=cfg.num_actions, **cut_fields(cut))


def _phi4_flash(cfg, cut=None):
    from distributed_ba3c_tpu.models.phi4_flash import Phi4Flash, cut_fields

    return Phi4Flash(num_actions=cfg.num_actions, **cut_fields(cut))


def _keye_vl2(cfg, cut=None):
    from distributed_ba3c_tpu.models.keye_vl2 import KeyeVL2, cut_fields

    return KeyeVL2(num_actions=cfg.num_actions, **cut_fields(cut))


def _olmo_hybrid(cfg, cut=None):
    from distributed_ba3c_tpu.models.olmo_hybrid import OlmoHybrid, cut_fields

    return OlmoHybrid(num_actions=cfg.num_actions, **cut_fields(cut))


def _nemotron_h(cfg, cut=None):
    from distributed_ba3c_tpu.models.nemotron_h import NemotronH, cut_fields

    return NemotronH(num_actions=cfg.num_actions, **cut_fields(cut))


MODELS: Dict[str, Callable] = {
    DEFAULT_MODEL: _ba3cnet, "lfm2-moe": _lfm2_moe, "phi4-flash": _phi4_flash,
    "keye-vl2": _keye_vl2, "olmo-hybrid": _olmo_hybrid,
    "nemotron-h": _nemotron_h,
}


def build_model(name: str, cfg, cut: str | None = None):
    """The policy ``--model name`` names, its action space from ``cfg``."""
    if name not in MODELS:
        raise ValueError(f"unknown model {name!r}; have {sorted(MODELS)}")
    return MODELS[name](cfg, cut)
