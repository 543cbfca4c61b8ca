"""The fused step's device scopes and host spans (utils/profiling.py).

Scopes are read where a capture reads them: in the ``op_name`` metadata of
the compiled HLO. Host spans are read from a real ``jax.profiler`` capture
taken on the CPU. No chip is needed; no time is measured here.
"""

import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest

from distributed_ba3c_tpu import audit
from distributed_ba3c_tpu.utils import profiling

_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _op_names(hlo_text):
    """[(instruction line, op_name)] of every instruction that has one."""
    out = []
    for line in hlo_text.splitlines():
        found = _OP_NAME.search(line)
        if found:
            out.append((line, found.group(1)))
    return out


def _small_step(grad_chunk_samples):
    """fused.step at the audit's canonical small shapes (2 envs a shard on
    the 2-device canonical mesh, 4 rollout steps), built for real arrays."""
    from distributed_ba3c_tpu.envs.jaxenv import pong
    from distributed_ba3c_tpu.fused.loop import (
        create_fused_state,
        make_fused_step,
    )

    cfg, model, opt = audit._canonical_parts()
    n = audit.CANONICAL_MESH_DEVICES
    step = make_fused_step(
        model, opt, cfg, audit.canonical_mesh(), pong, rollout_len=4,
        grad_chunk_samples=grad_chunk_samples,
    )
    state = create_fused_state(
        jax.random.PRNGKey(0), model, cfg, opt, pong, 2 * n, n_shards=n
    )
    return step, state


@pytest.fixture(scope="module")
def canonical_hlo():
    """The audit's own entry point: one chunk, so no accumulating scan."""
    target = audit.build_entry("fused.step")
    return target.jit_fn.lower(*target.args).compile().as_text()


@pytest.fixture(scope="module")
def chunked():
    """Two chunks a shard: the first chunk and the accumulating scan."""
    step, state = _small_step(grad_chunk_samples=4)
    state = step.put(state)
    hlo = step.audit_jit.lower(
        state, jnp.float32(0.01), jnp.float32(1e-3)
    ).compile().as_text()
    return step, state, hlo


#: open only where a shard's env batch is large enough for the forward to run
#: in sub-batches, which these small steps' is not
#: (tests/test_forward_sub_batch.py finds both in a step where it is)
_BY_SHAPE = (profiling.ROLLOUT_POLICY_SUB_BATCH, profiling.RETURNS_SUB_BATCH)


@pytest.mark.parametrize("which", ["canonical", "chunked"])
@pytest.mark.parametrize(
    "scope", [s for s in profiling.SCOPES if s not in _BY_SHAPE])
def test_every_scope_is_in_the_compiled_steps_op_names(
        canonical_hlo, chunked, which, scope):
    hlo = canonical_hlo if which == "canonical" else chunked[2]
    found = {profiling.scope_of(name) for _, name in _op_names(hlo)}
    assert scope in found or any(
        f and f.startswith(scope + "/") for f in found), sorted(map(str, found))


@pytest.mark.parametrize("which", ["canonical", "chunked"])
def test_learner_ops_come_with_and_without_transpose(
        canonical_hlo, chunked, which):
    hlo = canonical_hlo if which == "canonical" else chunked[2]
    learner = [
        name for _, name in _op_names(hlo)
        if (profiling.scope_of(name) or "").split("/")[0] == profiling.LEARNER
    ]
    backward = [n for n in learner if profiling.is_backward(n)]
    assert backward and len(backward) < len(learner)
    # the loss is differentiated too: both directions carry learner/loss
    loss = [n for n in learner if profiling.scope_of(n) == profiling.LEARNER_LOSS]
    assert any(profiling.is_backward(n) for n in loss)
    assert any(not profiling.is_backward(n) for n in loss)


@pytest.mark.parametrize("which", ["canonical", "chunked"])
def test_every_convolution_is_in_exactly_one_forward_or_learner_scope(
        canonical_hlo, chunked, which):
    hlo = canonical_hlo if which == "canonical" else chunked[2]
    homes = {profiling.ROLLOUT_POLICY: 0, profiling.RETURNS: 0, profiling.LEARNER: 0}
    convs = [
        (line, name) for line, name in _op_names(hlo) if " convolution(" in line
    ]
    # three forwards of four convs, and dx of all but the first; the CPU's
    # compiler rewrites the four dW convolutions and drops their metadata
    # (the v5e's keeps it: tests/benchmark/test_benchmark_scopes.py)
    assert len(convs) >= 4 + 4 + 4 + 3
    for line, name in convs:
        scope = profiling.scope_of(name)
        assert scope in homes, (scope, line[:200])
        homes[scope] += 1
    assert homes[profiling.ROLLOUT_POLICY] == homes[profiling.RETURNS] == 4
    learner = [n for _, n in convs if profiling.scope_of(n) == profiling.LEARNER]
    assert sum(profiling.is_backward(n) for n in learner) >= 3
    # one forward in the first chunk, one more in the accumulating scan
    assert sum(not profiling.is_backward(n) for n in learner) == (
        4 if which == "canonical" else 8)


@pytest.mark.parametrize("op_name,scope,backward", [
    ("jit(multi_step)/rollout/while/body/closed_call/policy/BA3CNet/Conv_0/conv_general_dilated",
     "rollout/policy", False),
    ("jit(multi_step)/rollout/while/body/closed_call/env_step/vmap(render)/mul:",
     "rollout/env_step/render", False),
    ("jit(multi_step)/rollout/while/body/closed_call/env_step/vmap()/while/body/closed_call/add",
     "rollout/env_step", False),
    ("jit(multi_step)/rollout/while", "rollout", False),
    ("jit(multi_step)/returns/BA3CNet/Dense_2/dot_general", "returns", False),
    ("jit(multi_step)/rollout/while/body/closed_call/policy/sub_batch/while/body/closed_call/BA3CNet/Conv_0/conv_general_dilated",
     "rollout/policy/sub_batch", False),
    ("jit(multi_step)/returns/sub_batch/while/body/closed_call/BA3CNet/Conv_3/conv_general_dilated",
     "returns/sub_batch", False),
    ("jit(multi_step)/learner/sub_batch/mul", "learner", False),  # no such scope there
    ("jit(multi_step)/while/body/closed_call/learner/transpose(jvp(BA3CNet))/Conv_1/conv_general_dilated",
     "learner", True),
    ("jit(multi_step)/learner/transpose(jvp(loss))/mul;learner/transpose(jvp(loss))",
     "learner/loss", True),
    ("jit(multi_step)/learner/jvp(loss)/jit(log_softmax)/sub", "learner/loss", False),
    ("jit(multi_step)/optimizer/sqrt", "optimizer", False),
    # the Pallas grouped products, exactly as a v5e capture names them (my
    # chip run, PR 30): the call keeps its scopes, and JAX's own mark of the
    # backward pass; the forward the backward runs again counts as backward
    ("jit(multi_step)/while/body/closed_call/learner/jvp(moe)/jit(_sorted_rows)/while/body/experts/gmm/jit(gmm)/gmm/pallas_call:",
     "learner/moe/experts/gmm", False),
    ("jit(multi_step)/learner/jvp(moe)/jit(_sorted_rows)/while/body/experts/gmm/jit(gmm)/gmm/pallas_call:",
     "learner/moe/experts/gmm", False),
    ("jit(multi_step)/while/body/closed_call/learner/transpose(jvp(learner))/jvp()/checkpoint/moe/jit(_sorted_rows)/while/body/jvp(experts)/gmm/jit(gmm)/gmm/pallas_call:",
     "learner/moe/experts/gmm", True),
    ("jit(multi_step)/while/body/closed_call/learner/transpose(jvp(learner))/jvp()/checkpoint/moe/jit(_sorted_rows)/while/body/transpose(jvp(experts))/gmm/jit(gmm)/gmm_dx/pallas_call:",
     "learner/moe/experts/gmm", True),
    ("jit(multi_step)/learner/transpose(jvp(learner))/jvp()/checkpoint/moe/jit(_sorted_rows)/while/body/transpose(jvp(experts))/gmm/jit(tgmm)/gmm_dw/pallas_call:",
     "learner/moe/experts/gmm", True),
    # the tile schedule computed for them lies in the same scope
    ("jit(multi_step)/learner/jvp(moe)/jit(_sorted_rows)/while/body/experts/gmm/jit(_roll_dynamic)/select_n",
     "learner/moe/experts/gmm", False),
    ("jit(multi_step)/optimizer/gmm/pallas_call:", "optimizer", False),  # no such scope there
    ("jit(multi_step)/BA3CNet/Conv_0/add", None, False),
    ("jit(create)/vmap(render)/mul", None, False),  # a render outside a rollout
    ("", None, False),
])
def test_scope_of_an_op_name(op_name, scope, backward):
    assert profiling.scope_of(op_name) == scope
    assert profiling.is_backward(op_name) is backward


@pytest.mark.parametrize("op_name,neighbour,scope,backward", [
    # the compiler's bare name: the scoped op that ran before it says where
    ("ragged-dot-none",
     "jit(multi_step)/learner/jvp(moe)/dispatch/jit(_where)/select_n",
     "learner/moe/experts", False),
    ("ragged-dot-metadata",
     "jit(multi_step)/learner/transpose(jvp(learner))/jvp()/checkpoint/moe/experts/mul",
     "learner/moe/experts", True),
    # inside a jit within the step the call's scopes stand in front of it
    ("jit(multi_step)/while/body/closed_call/learner/jvp(moe)/jit(_sorted_rows)/ragged-dot-none",
     "jit(multi_step)/optimizer/sqrt", "learner/moe/experts", False),
    ("jit(multi_step)/learner/transpose(jvp(learner))/jvp()/checkpoint/moe/jit(_sorted_rows)/ragged-dot-none",
     "", "learner/moe/experts", True),
    ("ragged-dot-none", "jit(multi_step)/optimizer/sqrt", None, False),
    ("jit(multi_step)/learner/jvp(moe)/experts/mul", "", None, False),  # no kernel
    # a Pallas call is no renamed kernel: its own name says where it lies
    ("jit(multi_step)/learner/jvp(moe)/jit(_sorted_rows)/while/body/experts/gmm/jit(gmm)/gmm/pallas_call:",
     "jit(multi_step)/optimizer/sqrt", None, False),
])
def test_a_renamed_kernels_scope(op_name, neighbour, scope, backward):
    assert profiling.kernel_scope(op_name, neighbour) == scope
    if scope is not None:
        marked = op_name if profiling.scope_of(op_name) else neighbour
        assert profiling.is_backward(marked) is backward


def test_scope_names_nest_as_their_paths_say():
    assert len(set(profiling.SCOPES)) == len(profiling.SCOPES)
    for scope in profiling.SCOPES:
        parent = scope.rsplit("/", 1)[0]
        assert parent == scope or parent in profiling.SCOPES
        assert scope.split("/")[0] in profiling.PHASES
    spans = [v for k, v in vars(profiling).items() if k.startswith("SPAN_") and k != "SPAN_PREFIX"]
    assert len(spans) == 6 and all(s.startswith(profiling.SPAN_PREFIX) for s in spans)


def _xplane(trace_dir):
    return sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]


@pytest.fixture(scope="module")
def cpu_capture(chunked, tmp_path_factory):
    """Three ``step()`` calls inside a capture, then two outside it."""
    step, state, _ = chunked
    state, m = step(state, 0.01, 1e-3)  # compiled before the capture opens
    jax.block_until_ready(m)
    out = str(tmp_path_factory.mktemp("capture"))
    jax.profiler.start_trace(out)
    try:
        for _ in range(3):
            state, m = step(state, 0.01, 1e-3)
        jax.block_until_ready(m)
    finally:
        jax.profiler.stop_trace()
    for _ in range(2):
        state, m = step(state, 0.01)
    jax.block_until_ready(m)
    return _xplane(out)


def test_host_spans_of_three_dispatches_are_in_the_capture(cpu_capture):
    spans = profiling.host_spans(cpu_capture)
    by_name = {}
    for name, start, dur in spans:
        by_name.setdefault(name, []).append((start, start + dur))
    assert set(by_name) == {
        profiling.SPAN_STEP, profiling.SPAN_STEP_HYPER, profiling.SPAN_STEP_ENQUEUE}
    assert all(len(v) == 3 for v in by_name.values()), by_name
    assert [r[1] for r in spans] == sorted(r[1] for r in spans)


@pytest.mark.parametrize("inner", [
    profiling.SPAN_STEP_HYPER, profiling.SPAN_STEP_ENQUEUE])
def test_a_dispatchs_parts_nest_in_it_on_the_captures_clock(cpu_capture, inner):
    from jax.profiler import ProfileData

    spans = profiling.host_spans(cpu_capture)
    outer = [r for r in spans if r[0] == profiling.SPAN_STEP]
    parts = [r for r in spans if r[0] == inner]
    for (_, lo, dur), (_, a, d) in zip(outer, parts):
        assert lo <= a and a + d <= lo + dur
    # hyper comes before enqueue inside one dispatch
    hyper = [r for r in spans if r[0] == profiling.SPAN_STEP_HYPER]
    enqueue = [r for r in spans if r[0] == profiling.SPAN_STEP_ENQUEUE]
    assert all(h[1] + h[2] <= e[1] for h, e in zip(hyper, enqueue))
    # the capture's clock: every span lies inside the capture's own events
    starts, ends = [], []
    for plane in ProfileData.from_file(cpu_capture).planes:
        for line in plane.lines:
            for e in line.events:
                starts.append(e.start_ns)
                ends.append(e.start_ns + e.duration_ns)
    assert min(starts) <= outer[0][1] and outer[-1][1] + outer[-1][2] <= max(ends)


def test_no_capture_open_no_span_and_other_prefixes_are_left_out(
        cpu_capture, tmp_path):
    # two dispatches ran after the capture closed: still three of each
    assert len(profiling.host_spans(cpu_capture)) == 9
    assert profiling.host_spans(cpu_capture, prefix="bench_") == []
    assert profiling.host_spans(
        cpu_capture, prefix=profiling.SPAN_STEP_HYPER
    ) == [r for r in profiling.host_spans(cpu_capture)
          if r[0] == profiling.SPAN_STEP_HYPER]
    # a span opened with no capture at all is inert
    with profiling.host_span(profiling.SPAN_EPOCH_FETCH):
        pass
    # a capture with no device plane has no op to sort by scope
    assert profiling.op_time_by_scope(cpu_capture) is None
