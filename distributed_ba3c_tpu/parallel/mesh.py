"""Device-mesh construction.

The reference's notion of topology is a ``tf.train.ClusterSpec`` of ps/worker
host:port strings (SURVEY.md §2.5 #15). The TPU-native equivalent is a
``jax.sharding.Mesh`` over the slice's devices; collectives ride ICI inside a
slice and DCN across hosts, chosen by XLA from the sharding — no address lists.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

# Axis names used throughout the framework.
DATA_AXIS = "data"    # batch / gradient data-parallel axis (the only one BA3C needs)
MODEL_AXIS = "model"  # reserved for tensor-parallel shardings of larger models


def shard_local(params, axis: str = DATA_AXIS):
    """The device-varying view of replicated ``params`` that every learner
    differentiates against (idempotent; inside ``jax.shard_map`` only; any
    pytree — the greedy evaluator marks its constant-built scan carries
    with it too).

    ``jax.grad`` w.r.t. a REPLICATED shard_map input makes the transpose
    insert one all-reduce per leaf per grad call — inside a
    gradient-accumulation scan (macro sub-batches, HBM-bounding chunks) that
    is one collective per sub-batch, not per update. Grads of the varying
    view stay shard-local, so the step accumulates locally and pays exactly
    ONE explicit ``jax.lax.psum`` per leaf per update, which is what
    tools/ba3caudit T3 counts."""

    def view(p):
        if axis in jax.typeof(p).vma:
            return p
        return jax.lax.pcast(p, (axis,), to="varying")

    return jax.tree_util.tree_map(view, params)


def make_mesh(
    num_data: Optional[int] = None,
    num_model: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a (data[, model]) mesh over the available devices.

    Defaults to a 1-D data-parallel mesh over every addressable device — the
    BA3C workload is pure DP (SURVEY.md §2.11: TP/PP/SP/EP are absent in the
    reference by construction; the model is a tiny convnet).
    """
    devices = list(devices if devices is not None else jax.devices())
    if num_data is None:
        num_data = len(devices) // num_model
    if num_data * num_model != len(devices):
        raise ValueError(
            f"mesh {num_data}x{num_model} does not cover {len(devices)} devices"
        )
    dev_array = np.asarray(devices).reshape(num_data, num_model)
    return Mesh(dev_array, (DATA_AXIS, MODEL_AXIS))
