"""What a token-sequence policy is apart from its mixers: the one home of
what models/{lfm2_moe,phi4_flash,keye_vl2,olmo_hybrid,nemotron_h,xing4}.py
share above models/layers.py's functions of arrays.

A policy is a frozen dataclass of its published fields that inherits
:class:`SequencePolicy` and writes its own layers' leaves, ``init_carry``,
``step``, ``unroll`` and mixers (docs/policy_protocol.md, "Adding a
policy"). From here it takes the cut lookup, ``for_env``, the seeded
parameters round its layers, the embedding, the head, the rollout's
snapshot, the parts of a decode step that no mixer owns, the unroll's
skeleton and the carry's byte-counter. Nothing here is traced as a program
of its own: every function is inlined where a policy calls it, under the
scope the policy has open.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from distributed_ba3c_tpu.models import layers
from distributed_ba3c_tpu.models.a3c import PolicyValue
from distributed_ba3c_tpu.ops import decode_attention
from distributed_ba3c_tpu.utils import profiling
from distributed_ba3c_tpu.utils.profiling import device_scope


VALUE_INIT_SCALE = 0.01


def cut_fields(cuts: dict, cut: str | None) -> dict:
    """The fields ``--model_cut cut`` sets, from a policy module's ``CUTS``;
    the first cut of ``cuts`` (the published widths' share of a chip) is
    what no ``--model_cut`` means."""
    cut = cut or next(iter(cuts))
    if cut not in cuts:
        raise ValueError(f"unknown --model_cut {cut!r}; have {sorted(cuts)}")
    return dict(cuts[cut])


class Seeded:
    """The seeded float32 initialisers of one ``init_params``. Each draws
    the next of the keys split from ``rng`` (``keys``, for a leaf of another
    law), so a leaf's value follows from the order the leaves are asked in."""

    def __init__(self, rng, layers: int):
        self.keys = iter(jax.random.split(rng, 16 * layers + 4))

    def normal(self, shape, fan_in):
        """A kernel: normal, scaled by 1/sqrt(fan_in)."""
        return jax.random.normal(
            next(self.keys), shape, jnp.float32) / math.sqrt(fan_in)

    def uniform(self, shape, low, high):
        return low + (high - low) * jax.random.uniform(
            next(self.keys), shape, jnp.float32)

    @staticmethod
    def ones(n: int):
        return jnp.ones((n,), jnp.float32)

    @staticmethod
    def zeros(n: int):
        return jnp.zeros((n,), jnp.float32)


# -- the decode step's parts that no mixer owns --------------------------------
def decode_opening(carry_pos, fresh):
    """(``pos``: each env's position with the fresh ones back at 0; ``keep``
    [B] bool: false where ``fresh``, which zeroes whatever state an episode
    must not inherit)."""
    return jnp.where(fresh, 0, carry_pos), ~fresh


def write_row(rows, cache, at, new):
    """``cache`` [B, P, ...] with row ``at[b]`` of env ``b`` set to
    ``new[b]``, in place: one row an env (``rows`` is ``arange(B)``, made
    once a step)."""
    return cache.at[rows, at].set(
        new.reshape(rows.shape[0], *cache.shape[2:]),
        indices_are_sorted=True, unique_indices=True)


def with_its_input(p, x):
    """A layer's weights ``p`` and its input ``x``, tied together: what the
    layer computes from ``p`` then waits for ``x`` (an unroll's ``tie``).
    The trainer's learner runs ``unroll`` once a chunk of envs in a loop,
    where a weight's cast to the compute type is the same in every trip, and
    the compiler lifts every one of them out of the loop and holds them
    through it, in two layouts (forward and transposed): 2.6 GB at
    ``nemotron-h``'s 667 M parameters, with which a chunk of 2 envs compiled
    to 18.0 GB of a v5e's 16.9 and without which to 14.7 (PERF.md section 4,
    PR 44). Tied to the input a cast is made where it is used and dies
    there."""
    return jax.lax.optimization_barrier((p, x))


class SequencePolicy:
    """The base of the token-sequence policies' dataclasses. A policy sets
    the three class attributes below where it differs and names its norm's
    epsilon by its published field (:attr:`final_norm_eps`)."""

    carries_state = True
    #: the parameter group whose ``table`` the head multiplies by: the
    #: embedding where the head is tied, ``"head"`` where it has its own rows
    head_table = "embed"
    #: leaves of two or more dimensions that the rollout's snapshot leaves
    #: float32 (vectors and the value head always are)
    float32_leaves = ()

    # -- the policy's own (with ``unroll``, which hands ``_unroll`` its layer) --
    @property
    def final_norm_eps(self) -> float:
        raise NotImplementedError

    def _init_layer(self, i: int, init: "Seeded") -> dict:
        """Held layer ``i``'s seeded float32 leaves, ``{leaf: array}``."""
        raise NotImplementedError

    def init_carry(self, batch: int):
        """What decoding carries, an env a row: a NamedTuple, ``pos`` first."""
        raise NotImplementedError

    def step(self, params, obs, carry, fresh):
        """One token an env: ``obs`` [B] int32, ``fresh`` [B] bool (the
        token opens an episode: forget the last one first) -> (PolicyValue,
        the carry one position on)."""
        raise NotImplementedError

    # -- what every policy takes from here --------------------------------------
    def for_env(self, env):
        """This policy over ``env``'s action space and episode length."""
        return dataclasses.replace(
            self, num_actions=env.num_actions, max_positions=env.episode_length
        )

    def layer_name(self, i: int) -> str:
        return f"layer_{self.layer_ids[i]}"

    def init_params(self, rng):
        """Seeded float32 parameters, ``{layer: {leaf: array}}``: the
        embedding's rows of the ids held, each held layer's leaves
        (``_init_layer(i, init)``, the policy's), the final norm's unit
        gain, the head's own rows where it is not tied, the value head."""
        d, ids = self.hidden_size, self.num_actions
        init = Seeded(rng, len(self.layer_ids))
        params = {"embed": {"table": init.normal((ids, d), d)}}
        for i in range(len(self.layer_ids)):
            params[self.layer_name(i)] = self._init_layer(i, init)
        params["final"] = {"norm": init.ones(d)}
        if self.head_table != "embed":
            params[self.head_table] = {"table": init.normal((ids, d), d)}
        # a value head that starts near zero, as actor-critic code starts it:
        # at unit scale V ~ N(0, 1) against returns of 0 swamps the advantage
        params["value"] = {"kernel": VALUE_INIT_SCALE * init.normal((d, 1), d),
                           "bias": init.zeros(1)}
        return params

    def rollout_params(self, params):
        """The matrices in the compute type, once for a whole rollout: a
        decode step then reads 2 bytes a weight and not 4."""
        return layers.matrices_in(
            params, self.compute_dtype, keep=self.float32_leaves)

    def _mm(self, x, w, out_dtype=jnp.float32):
        return layers.mm(x, w, self.compute_dtype, out_dtype)

    def _embed(self, params, tokens):
        return layers.embed_rows(
            params["embed"]["table"], tokens, self.compute_dtype)

    def streams_in(self, x):
        """The residual path a layer is handed, from the embedding's rows
        ``x`` [..., d]. One stream, the rows themselves, unless the policy
        keeps several (models/xing4.py: a tuple of ``n`` arrays [..., d])."""
        return x

    def streams_out(self, x):
        """What the head reads [..., d], from the residual path the last
        layer left: the inverse of :meth:`streams_in` in shape."""
        return x

    def _head(self, params, x):
        """x [N, d] float32 -> PolicyValue over the held vocabulary: the
        final norm (a LayerNorm where the parameters hold its bias, else an
        RMSNorm), the head's table and the trainer's value head."""
        with device_scope(profiling.HEAD):
            final = params["final"]
            if "norm_b" in final:
                h = layers.layer_norm(
                    x, final["norm"], final["norm_b"], self.final_norm_eps)
            else:
                h = layers.rms_norm(x, final["norm"], self.final_norm_eps)
            logits, value = layers.tied_head(
                h, params[self.head_table]["table"], params["value"],
                self.compute_dtype)
            return PolicyValue(logits=logits, value=value)

    # -- the rollout's decode step ---------------------------------------------
    def _attend_rows(self, p, q, k_cache, v_cache, pos, kept=None):
        """One query an env over rows ``[0, pos]`` of its buffers (of those,
        the rows ``kept`` keeps), then ``W_o``: q [B, 1, H, D]; k_cache,
        v_cache [B, P, KV * D] -> [B, d] float32."""
        out = decode_attention.decode_attend(
            q[:, 0], k_cache, v_cache, pos + 1,
            1.0 / math.sqrt(self.head_dim), kept)
        return self._mm(out.reshape(q.shape[0], -1), p["wo"])

    def _decode_attention(self, p, qkv, caches, rows, pos):
        """A full-attention layer's decode: this position's K and V written
        into the buffers, the query over the rows up to it. ``qkv``: q [B, 1,
        H, D], k, v [B, 1, KV, D] -> ([B, d] float32, the buffers)."""
        q, k, v = qkv
        k_cache, v_cache = caches
        k_cache, v_cache = (write_row(rows, k_cache, pos, k),
                            write_row(rows, v_cache, pos, v))
        return self._attend_rows(p, q, k_cache, v_cache, pos), (k_cache, v_cache)

    def _carry_bytes(self, kinds):
        """Bytes of carry an env, one count for each subtree of the carry
        that ``kinds(carry)`` names: a constant of the shapes."""
        shapes = jax.eval_shape(lambda: self.init_carry(1))
        return tuple(
            sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(tree))
            for tree in kinds(shapes))

    # -- the learner's unroll ----------------------------------------------------
    def _unroll(self, params, tokens, layer, took=None, aux=None, side=None,
                tie=None):
        """Whole episodes from a reset, ``tokens`` [B, T] int32 ->
        (PolicyValue with logits [B, T, A] and value [B, T], aux): the
        embedding, the held layers one at a time, the head.

        ``layer(i, p, x) -> (x, second)`` is held layer ``i`` over x [B, T,
        d] float32 (of a policy with several residual streams: what its
        :meth:`streams_in` makes of the embedding, until its
        :meth:`streams_out` hands the head one row a token). It is recomputed
        in the backward, always: no published cut fits the chip with every
        layer's activations kept. ``second`` is the policy's:
        ``took(second)`` is handed it as each layer returns and ``aux()``
        makes the unroll's ``aux`` of what was taken, after the head. A policy whose layers hand each other more than the
        residual stream names those channels' start in ``side``; its layer
        is then ``layer(i, p, x, *side) -> (x, side)``. ``tie(p, x) -> (p,
        x)`` ties a layer's, and the head's, weights to their input
        (:func:`with_its_input`, ROADMAP D23)."""
        B, T = tokens.shape
        x = self.streams_in(self._embed(params, tokens))
        threaded, side = side is not None, side or ()
        tie = tie or (lambda p, x: (p, x))
        for i in range(len(self.layer_ids)):
            run = jax.checkpoint(
                lambda p, x, *side, i=i: layer(i, *tie(p, x), *side))
            x, second = run(params[self.layer_name(i)], x, *side)
            if threaded:
                side = second
            elif took is not None:
                took(second)
        top, x = tie(
            {k: params[k] for k in ("final", self.head_table, "value")},
            self.streams_out(x))
        out = self._head(top, x.reshape(B * T, -1))
        aux = aux() if aux is not None else {}
        return PolicyValue(
            logits=out.logits.reshape(B, T, -1), value=out.value.reshape(B, T)
        ), aux
