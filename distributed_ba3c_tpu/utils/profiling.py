"""The package's one home for ``jax.profiler``: names, spans, and the reader.

Reference equivalent (SURVEY.md §5): nothing built-in — op-level profiling
was offline (VTune/TF timeline). The rebuild uses what XLA ships:

- the **scope names** of the fused step's phases (``ROLLOUT`` ... ``METRICS``)
  and :func:`device_scope`, a ``jax.named_scope`` that puts one on the
  ``op_name`` metadata of every HLO instruction traced inside it. Metadata
  only: the compiled program is the same with or without them.
- the **span names** of the fused trainer's host work (``SPAN_*``) and
  :func:`host_span`, a ``jax.profiler.TraceAnnotation``: it lands in the host
  planes of the same ``.xplane.pb`` as the device ops, so host spans and
  device ops share one clock by construction. Inert while no capture is open.
- :func:`start_server` — ``jax.profiler`` trace server (``--profiler_port``);
  :func:`step_annotation` — per-step regions of the plane trainer.
- the **reader** of a capture (``.xplane.pb``), with nothing but JAX:
  :func:`op_time_by_scope`, :func:`host_spans`, and :func:`scope_of`, the
  rule by which an op gets its scope.

See docs/observability.md "Reading a device capture of the fused trainer".
"""

from __future__ import annotations

import contextlib
import re
from typing import Dict, Iterable, List, Optional, Tuple

import jax
from jax.profiler import ProfileData

from distributed_ba3c_tpu.utils import logger

# -- device scopes of fused.step (fused/loop.py, envs/jaxenv/pong.py) --------
# A name is the path a reader reports; ``device_scope`` opens its last
# component, so a scope nested in code nests in the name.
ROLLOUT = "rollout"
ROLLOUT_POLICY = "rollout/policy"
#: open only where the forward ran in sub-batches (fused/loop.py
#: ``sub_batched``): time here says that it did
ROLLOUT_POLICY_SUB_BATCH = "rollout/policy/sub_batch"
ROLLOUT_SAMPLE = "rollout/sample"
ROLLOUT_ENV_STEP = "rollout/env_step"
ROLLOUT_RENDER = "rollout/env_step/render"
ROLLOUT_STACK = "rollout/stack"
RETURNS = "returns"
RETURNS_SUB_BATCH = "returns/sub_batch"  # the bootstrap forward, likewise
LEARNER = "learner"
LEARNER_LOSS = "learner/loss"
GRAD_REDUCE = "grad_reduce"
OPTIMIZER = "optimizer"
METRICS = "metrics"
#: the phases of one update, in program order: every scoped op is in one
PHASES = (ROLLOUT, RETURNS, LEARNER, GRAD_REDUCE, OPTIMIZER, METRICS)
SCOPES = (
    ROLLOUT, ROLLOUT_POLICY, ROLLOUT_POLICY_SUB_BATCH, ROLLOUT_SAMPLE,
    ROLLOUT_ENV_STEP, ROLLOUT_RENDER, ROLLOUT_STACK, RETURNS,
    RETURNS_SUB_BATCH, LEARNER, LEARNER_LOSS, GRAD_REDUCE, OPTIMIZER, METRICS,
)
# -- scopes inside a layered sequence policy (models/lfm2_moe.py, ops/moe.py,
# models/phi4_flash.py, ops/ssm.py), opened under ``rollout/policy`` (the
# decode step) and under ``learner`` (the unroll) alike; a conv policy's step
# has none of them, so they are kept apart from SCOPES, which every fused
# step carries
EMBED = "embed"
OP_CONV = "op_conv"
OP_ATTN = "op_attn"
FFN_DENSE = "ffn_dense"
MOE = "moe"
MOE_ROUTER = "moe/router"
MOE_DISPATCH = "moe/dispatch"
MOE_EXPERTS = "moe/experts"
#: open only round the Pallas grouped-product kernels
#: (ops/grouped_matmul.py): time here says that they ran, none that the
#: products went to ``jax.lax.ragged_dot`` (another backend, a small cut)
MOE_EXPERTS_GMM = "moe/experts/gmm"
MOE_COMBINE = "moe/combine"
#: a shared expert beside the routed ones (models/nemotron_h.py): a dense
#: product every token takes, the policy's own and none of ops/moe.py's
MOE_SHARED = "moe/shared"
HEAD = "head"
#: a state-space (Mamba) mixer and its parts: norm + input projection, the
#: causal depthwise conv + silu, the selective scan alone (ops/ssm.py; the
#: ``dt``/``B``/``C`` projections lie in ``op_ssm`` outside the four), gate +
#: output projection
OP_SSM = "op_ssm"
OP_SSM_IN_PROJ = "op_ssm/in_proj"
OP_SSM_CONV = "op_ssm/conv"
OP_SSM_SCAN = "op_ssm/scan"
OP_SSM_OUT_PROJ = "op_ssm/out_proj"
#: a gated memory unit: reads what a state-space layer before it produced
OP_GMU = "op_gmu"
#: attention over a ring of the last ``window`` positions; over every past
#: position, writing the K/V that later layers share; queries only, over
#: that shared K/V
OP_ATTN_WINDOW = "op_attn_window"
OP_ATTN_FULL = "op_attn_full"
OP_ATTN_CROSS = "op_attn_cross"
#: open only round the Pallas kernel of a decode step's attention
#: (ops/decode_attention.py), inside each of the three above: time here says
#: that the rows up to the position were read, none that whole buffers went
#: to ``layers.attend`` under a mask (another backend, a small cut)
DECODE_ATTEND = "decode_attend"
OP_ATTN_DECODE = tuple(
    f"{layer}/{DECODE_ATTEND}"
    for layer in (OP_ATTN_WINDOW, OP_ATTN_FULL, OP_ATTN_CROSS))
#: grouped-query attention over the keys an indexer selects
#: (models/keye_vl2.py): projections, norms, RoPE, the attention under the
#: selection's mask, ``W_o``; inside it, as in the three above, the scope
#: that is open only round the decode's Pallas kernel
OP_ATTN_SPARSE = "op_attn_sparse"
OP_ATTN_SPARSE_DECODE = f"{OP_ATTN_SPARSE}/{DECODE_ATTEND}"
#: the indexer beside it: its projections; ``scores`` (``sum_j w_j
#: relu(q_j . k)`` against every live key), ``select`` (the exact top-k),
#: ``loss`` (its KL against the main attention's distribution). Inside
#: ``select``, ``radix`` is open only round the two searches by bits
#: (ops/topk_select.py): time there says they ran, none in an episode's
#: first top-k positions that the mask was ``live`` and they were skipped
OP_INDEXER = "op_indexer"
OP_INDEXER_SCORES = "op_indexer/scores"
OP_INDEXER_SELECT = "op_indexer/select"
OP_INDEXER_SELECT_RADIX = "op_indexer/select/radix"
OP_INDEXER_LOSS = "op_indexer/loss"
#: a gated delta-rule linear-attention mixer (models/olmo_hybrid.py with
#: ops/delta_rule.py): ``in_proj`` (``W_qkv``, ``W_z``, the gates' ``W_a`` and
#: ``W_b``), ``conv``, ``delta`` (the recurrence alone: one position from the
#: carried state in the decode step, the chunked form in the unroll), ``out``
#: (the gated per-head norm and ``W_o``). That policy's full-attention layer
#: opens ``op_attn_full``, with ``decode_attend`` inside it round the kernel
OP_LINATTN = "op_linattn"
OP_LINATTN_IN_PROJ = "op_linattn/in_proj"
OP_LINATTN_CONV = "op_linattn/conv"
OP_LINATTN_DELTA = "op_linattn/delta"
DELTA_CHUNKS = "delta_chunks"
OP_LINATTN_DELTA_KERNEL = f"{OP_LINATTN_DELTA}/{DELTA_CHUNKS}"
OP_LINATTN_OUT = "op_linattn/out"
#: a Mamba-2 mixer (models/nemotron_h.py with ops/ssd.py): ``in_proj`` (the
#: block's norm and ``W_in``, the step sizes), ``conv``, ``ssd`` (the
#: recurrence alone: one position from the carried state in the decode step,
#: the chunked form in the unroll), ``out`` (the gated group norm and
#: ``W_out``). Inside ``ssd``, ``ssd_chunks`` is open only round the
#: chunked form's Pallas kernels (ops/ssd.py), forward and backward: time
#: there says they ran, none that the plain ``jax.numpy`` form did (another
#: backend, a small cut). That policy's attention block opens
#: ``op_attn_full`` with ``decode_attend`` inside it, its expert blocks
#: ``moe`` with ``moe/shared``
OP_MAMBA2 = "op_mamba2"
OP_MAMBA2_IN_PROJ = "op_mamba2/in_proj"
OP_MAMBA2_CONV = "op_mamba2/conv"
OP_MAMBA2_SSD = "op_mamba2/ssd"
SSD_CHUNKS = "ssd_chunks"
OP_MAMBA2_SSD_KERNEL = f"{OP_MAMBA2_SSD}/{SSD_CHUNKS}"
OP_MAMBA2_OUT = "op_mamba2/out"
#: latent attention (models/xing4.py): ``q`` (the query's down-projection,
#: its norm, its up-projection, RoPE), ``kv_latent`` (the keys' and values'
#: shared down-projection, the latent's norm, the shared key's RoPE and, in
#: the decode, the cache row's write), ``expand`` (the unroll alone: keys and
#: values of every head from the latent, ``W_kvb``), ``absorb`` (the decode
#: alone: ``W_kvb`` taken into the query and out of the attended latent, so
#: that no key or value is ever formed), ``attend`` (scores, softmax, the
#: weighted sum; in the decode over the cache's latent rows, with
#: ``decode_attend`` inside it round the kernel), ``out`` (``W_o``)
OP_MLA = "op_mla"
OP_MLA_Q = "op_mla/q"
OP_MLA_KV_LATENT = "op_mla/kv_latent"
OP_MLA_EXPAND = "op_mla/expand"
OP_MLA_ABSORB = "op_mla/absorb"
OP_MLA_ATTEND = "op_mla/attend"
OP_MLA_ATTEND_DECODE = f"{OP_MLA_ATTEND}/{DECODE_ATTEND}"
OP_MLA_OUT = "op_mla/out"
#: a residual path of several streams (ops/hyper_connection.py), round
#: every sub-block of models/xing4.py: ``mappings`` (the streams' norm, the
#: projection, the sigmoids, the Sinkhorn iterations), ``mix`` (the
#: sub-block's input read from the streams; its output written into them and
#: the streams mixed)
HYPER_CONN = "hyper_conn"
HYPER_CONN_MAPPINGS = "hyper_conn/mappings"
HYPER_CONN_MIX = "hyper_conn/mix"
#: the layers each sequence policy opens (models/lfm2_moe.py with ops/moe.py;
#: models/phi4_flash.py with ops/ssm.py; models/keye_vl2.py with ops/moe.py;
#: models/olmo_hybrid.py with ops/delta_rule.py; models/nemotron_h.py with
#: ops/ssd.py and ops/moe.py; models/xing4.py with ops/hyper_connection.py
#: and ops/moe.py): a step holds its own policy's
LFM2_LAYERS = (
    EMBED, OP_CONV, OP_ATTN, FFN_DENSE, MOE, MOE_ROUTER, MOE_DISPATCH,
    MOE_EXPERTS, MOE_EXPERTS_GMM, MOE_COMBINE, HEAD,
)
PHI4_FLASH_LAYERS = (
    EMBED, OP_SSM, OP_SSM_IN_PROJ, OP_SSM_CONV, OP_SSM_SCAN, OP_SSM_OUT_PROJ,
    OP_GMU, OP_ATTN_WINDOW, OP_ATTN_FULL, OP_ATTN_CROSS, *OP_ATTN_DECODE,
    FFN_DENSE, HEAD,
)
KEYE_VL2_LAYERS = (
    EMBED, OP_ATTN_SPARSE, OP_ATTN_SPARSE_DECODE, OP_INDEXER,
    OP_INDEXER_SCORES, OP_INDEXER_SELECT, OP_INDEXER_SELECT_RADIX,
    OP_INDEXER_LOSS, MOE, MOE_ROUTER, MOE_DISPATCH, MOE_EXPERTS,
    MOE_EXPERTS_GMM, MOE_COMBINE, HEAD,
)
OLMO_HYBRID_LAYERS = (
    EMBED, OP_LINATTN, OP_LINATTN_IN_PROJ, OP_LINATTN_CONV, OP_LINATTN_DELTA,
    OP_LINATTN_OUT, OP_ATTN_FULL, f"{OP_ATTN_FULL}/{DECODE_ATTEND}", FFN_DENSE,
    HEAD, OP_LINATTN_DELTA_KERNEL,
)
#: (no ``op_attn_full/decode_attend``: that scope is open round the decode's
#: kernel alone, which takes 8 query heads a K/V head and this policy has 16)
NEMOTRON_H_LAYERS = (
    EMBED, OP_MAMBA2, OP_MAMBA2_IN_PROJ, OP_MAMBA2_CONV, OP_MAMBA2_SSD,
    OP_MAMBA2_OUT, OP_ATTN_FULL, MOE, MOE_ROUTER, MOE_DISPATCH, MOE_EXPERTS,
    MOE_EXPERTS_GMM, MOE_COMBINE, MOE_SHARED, HEAD, OP_MAMBA2_SSD_KERNEL,
)
XING4_LAYERS = (
    EMBED, OP_MLA, OP_MLA_Q, OP_MLA_KV_LATENT, OP_MLA_EXPAND, OP_MLA_ABSORB,
    OP_MLA_ATTEND, OP_MLA_ATTEND_DECODE, OP_MLA_OUT, HYPER_CONN,
    HYPER_CONN_MAPPINGS, HYPER_CONN_MIX, FFN_DENSE, MOE, MOE_ROUTER,
    MOE_DISPATCH, MOE_EXPERTS, MOE_EXPERTS_GMM, MOE_COMBINE, MOE_SHARED, HEAD,
)
#: every policy's layers, each once, in the order they are first named
POLICY_LAYERS = tuple(dict.fromkeys(
    LFM2_LAYERS + PHI4_FLASH_LAYERS + KEYE_VL2_LAYERS + OLMO_HYBRID_LAYERS
    + NEMOTRON_H_LAYERS + XING4_LAYERS))
#: the rollout's once-an-update bfloat16 snapshot of the matrix weights
ROLLOUT_WEIGHTS_BF16 = "rollout/weights_bf16"


def policy_scope(under: str, layer: str) -> str:
    """``rollout/policy/moe/experts`` of (ROLLOUT_POLICY, MOE_EXPERTS)."""
    return f"{under}/{layer}"


SEQUENCE_SCOPES = (ROLLOUT_WEIGHTS_BF16,) + tuple(
    policy_scope(under, layer)
    for under in (ROLLOUT_POLICY, LEARNER) for layer in POLICY_LAYERS
)
#: every scope the reader sorts time into
ALL_SCOPES = SCOPES + SEQUENCE_SCOPES
#: reader-only splits of ``learner``: JAX marks the backward pass itself
LEARNER_FWD = "learner:fwd"
LEARNER_BWD = "learner:bwd"
UNSCOPED = "unscoped"

# -- host spans of the fused trainer ----------------------------------------
SPAN_PREFIX = "fused."
SPAN_STEP = "fused.step"
SPAN_STEP_HYPER = "fused.step.hyper"
SPAN_STEP_ENQUEUE = "fused.step.enqueue"
SPAN_EPOCH_FETCH = "fused.epoch.fetch"
SPAN_EPOCH_EVAL = "fused.epoch.eval"
SPAN_EPOCH_CHECKPOINT = "fused.epoch.checkpoint"


#: how many of ``step()``'s first calls also put the host's clock round their
#: three spans (a flight-recorder ``startup`` event each, ``fused.step#<k>``:
#: utils/backend.py); from the next call on ``step()`` reads no clock
RECORDED_STEP_CALLS = 4
_step_calls = 0


def count_step_call() -> int:
    """One more call of a fused ``step()`` in this process; -> which one."""
    global _step_calls
    _step_calls += 1
    return _step_calls


def step_calls() -> int:
    """Calls of a fused ``step()`` in this process so far: the position of a
    compiler event on the program's own timeline (utils/backend.py)."""
    return _step_calls


def device_scope(name: str):
    """``jax.named_scope`` of one of the scope names above (context manager
    or decorator)."""
    return jax.named_scope(name.rsplit("/", 1)[-1])


def host_span(name: str):
    """A host span in the profiler's own trace, on the device trace's clock."""
    return jax.profiler.TraceAnnotation(name)


def start_server(port: int) -> None:
    """Start the jax.profiler gRPC server (TensorBoard-attachable)."""
    jax.profiler.start_server(port)
    logger.info("jax.profiler server listening on :%d", port)


@contextlib.contextmanager
def step_annotation(
    name: str,
    step: int,
    trace_id: int = None,
    span_id: int = None,
):
    """Named trace region for one step (shows up in captured timelines).

    ``trace_id``/``span_id`` correlate a chip-session ``jax.profiler``
    capture with the host-side trace plane (telemetry/tracing.py): pass
    the active block trace's ids (``tracing.current_trace_id()``, or a
    TraceRef's fields) and the device timeline's step region carries them
    as metadata — line the Perfetto export of ``scripts/trace_dump.py``
    up against the XLA capture by matching the ids (ROADMAP item 1's
    on-chip captures land next to host spans instead of in a vacuum)."""
    kwargs = {"step_num": step}
    if trace_id is not None:
        kwargs["trace_id"] = int(trace_id)
    if span_id is not None:
        kwargs["span_id"] = int(span_id)
    with jax.profiler.StepTraceAnnotation(name, **kwargs):
        yield


# -- reading a capture --------------------------------------------------------
# How an op gets its scope on the v5e (looked at by hand, PR 24). The device
# plane's ``XLA Ops`` events are named by the instruction's whole HLO text,
# which has no ``metadata={...}``; ``jax.profiler.ProfileData`` shows an
# event's own stats (offset, duration) and not those of its *event metadata*;
# the capture embeds no HLO module (``/host:metadata`` is empty). But every
# instruction's event metadata does carry the stat ``tf_op``: the
# instruction's ``op_name``, scopes and all. So the reader takes events and
# their times from ``ProfileData`` and the ``tf_op`` of each event name from
# the file itself: an ``.xplane.pb`` is a protobuf (``XSpace``), and the few
# fields needed are read off its wire format below, with no schema and
# nothing beyond the standard library.

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
OP_NAME_STAT = "tf_op"
_OPCODE = re.compile(r"(?<=\s)([a-z][a-z0-9\-]*)\(")
#: an event of these opcodes spans its body's events: never summed as work
_CONTAINERS = ("while", "conditional", "call")


def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf) -> Iterable[Tuple[int, object]]:
    """(field number, value) of one protobuf message: an int for a varint,
    a memoryview for a length-delimited field; fixed-width ones skipped."""
    i, end = 0, len(buf)
    while i < end:
        tag, i = _varint(buf, i)
        number, wire = tag >> 3, tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield number, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield number, buf[i:i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")


def _map_entry(buf) -> Tuple[int, object]:
    key = value = None
    for number, v in _fields(buf):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _plane_op_names(plane) -> Tuple[str, Dict[str, str]]:
    """(plane name, {event name: op_name}) of one ``XPlane`` message.

    XPlane: name=2, lines=3 (skipped unread), event_metadata=4 and
    stat_metadata=5 (maps id -> message). XEventMetadata: name=2, stats=5.
    XStatMetadata: name=2. XStat: metadata_id=1, str_value=5, ref_value=7
    (the id of a stat metadata whose name is the string)."""
    name, events, stat_names = "", [], {}
    for number, v in _fields(plane):
        if number == 2:
            name = bytes(v).decode()
        elif number == 4:
            events.append(_map_entry(v)[1])
        elif number == 5:
            key, meta = _map_entry(v)
            for n, s in _fields(meta):
                if n == 2:
                    stat_names[key] = bytes(s).decode()
    out: Dict[str, str] = {}
    if not name.startswith(DEVICE_PLANE_PREFIX):
        return name, out
    for meta in events:
        event_name, op_name = None, None
        for number, v in _fields(meta):
            if number == 2:
                event_name = bytes(v).decode()
            elif number == 5:
                stat = dict(_fields(v))
                if stat_names.get(stat.get(1)) != OP_NAME_STAT:
                    continue
                if 5 in stat:
                    op_name = bytes(stat[5]).decode()
                elif 7 in stat:
                    op_name = stat_names.get(stat[7])
        if event_name and op_name:
            out[event_name] = op_name
    return name, out


def event_op_names(xplane_path: str) -> Dict[str, Dict[str, str]]:
    """{device plane: {event name: op_name}} of a capture."""
    with open(xplane_path, "rb") as f:
        space = memoryview(f.read())
    return dict(
        _plane_op_names(plane) for number, plane in _fields(space) if number == 1
    )


def _unwrap(component: str) -> str:
    """``transpose(jvp(loss))`` -> ``loss``: JAX wraps the names inside a
    transformed function in the transformation's own."""
    while component.endswith(")") and "(" in component:
        component = component[component.index("(") + 1:-1]
    return component


def scope_of(op_name: str) -> Optional[str]:
    """The deepest scope of :data:`ALL_SCOPES` an ``op_name`` lies in, or None.

    ``jit(multi_step)/rollout/while/body/closed_call/env_step/vmap(render)/..``
    is ``rollout/env_step/render``: the first component that names a phase,
    then each later component that names a scope inside the one so far."""
    names = [_unwrap(c) for c in re.split(r"[/;]", op_name.rstrip(":"))]
    for i, name in enumerate(names):
        if name in PHASES:
            break
    else:
        return None
    scope = names[i]
    for name in names[i + 1:]:
        if f"{scope}/{name}" in ALL_SCOPES:
            scope = f"{scope}/{name}"
    return scope


#: op_names the TPU compiler gives the kernels it makes of ``ragged_dot``
#: (``ragged-dot-none``, ``ragged-dot-metadata``): it drops the scopes the
#: instruction was traced under (by hand on a capture, PR 26). Inside a
#: ``jax.jit`` within the step (``ops/moe.py:_sorted_rows``) the name the
#: compiler gave comes last, after the scopes of the call (PR 28)
RENAMED_KERNEL = "ragged-dot"


def is_renamed_kernel(op_name: str) -> bool:
    return op_name.rsplit("/", 1)[-1].startswith(RENAMED_KERNEL)


def kernel_scope(op_name: str, neighbour_op_name: str) -> Optional[str]:
    """The scope of a grouped-product kernel the compiler renamed: the
    ``moe/experts`` of the ``moe`` scope its call lies in where the name
    still says so, else of whichever ``moe`` scope the scoped op that ran
    just before it lies in (its dispatch, or the products' own elementwise
    work)."""
    if not is_renamed_kernel(op_name):
        return None
    scope = scope_of(op_name) or scope_of(neighbour_op_name) or ""
    parts = scope.split("/")
    if MOE not in parts:
        return None
    return "/".join(parts[:parts.index(MOE)] + MOE_EXPERTS.split("/"))


def is_backward(op_name: str) -> bool:
    """JAX's own mark of the backward pass on an ``op_name``."""
    return "transpose(" in op_name


def _is_container(hlo_text: str) -> bool:
    found = _OPCODE.search(" " + hlo_text.split(" = ", 1)[-1])
    return bool(found) and found.group(1) in _CONTAINERS


def op_time_by_scope(xplane_path: str) -> Optional[dict]:
    """Device op time of a capture by scope; None if no op carries a scope.

    -> ``seconds``: {scope: device seconds a chip} for every scope of
    :data:`ALL_SCOPES` (a scope's time includes the scopes nested in it) plus
    :data:`LEARNER_FWD` / :data:`LEARNER_BWD` (``learner`` without and with
    JAX's ``transpose(``) and :data:`UNSCOPED`; ``total_s``: all op time a
    chip; ``unscoped_share``; ``unscoped_ops``: the ten unscoped ops with
    most time, [name, seconds]; ``events``: {chip: [events on its ``XLA
    Ops`` line, first start in ns]}, by which a caller can tell whether
    this is the capture it thinks it is.

    A ``while``/``conditional``/``call`` event spans its body's events and
    is never summed. A fusion is charged to the scope its own instruction
    carries (XLA gives a fusion its root's metadata), so a fusion across a
    scope boundary goes whole to one side; what carries no scope at all is
    reported, not assumed zero. Times are means over the chips."""
    op_names = event_op_names(xplane_path)
    seconds = dict.fromkeys(ALL_SCOPES + (LEARNER_FWD, LEARNER_BWD, UNSCOPED), 0.0)
    unscoped: Dict[str, float] = {}
    events: Dict[str, list] = {}
    total = 0.0
    scoped_ops = 0
    for plane in ProfileData.from_file(xplane_path).planes:
        if not plane.name.startswith(DEVICE_PLANE_PREFIX):
            continue
        names = op_names.get(plane.name, {})
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            count, first = 0, None
            by_event: Dict[str, float] = {}  # an instruction's text -> seconds
            before: Dict[str, str] = {}  # a renamed kernel -> its neighbour
            last_scoped = ""
            for e in sorted(line.events, key=lambda e: e.start_ns):
                count += 1
                if first is None:
                    first = e.start_ns
                by_event[e.name] = by_event.get(e.name, 0.0) + e.duration_ns / 1e9
                op_name = names.get(e.name, "")
                if is_renamed_kernel(op_name):
                    before.setdefault(e.name, last_scoped)
                elif not _is_container(e.name) and scope_of(op_name):
                    last_scoped = op_name
            events[plane.name] = [count, int(first) if count else None]
            for text, s in by_event.items():
                if _is_container(text):
                    continue
                total += s
                op_name = names.get(text, "")
                scope = scope_of(op_name)
                if text in before:
                    # an instruction sits at one place of the program: the
                    # neighbour of its first execution is its neighbour
                    bare = scope is None  # no scopes of a call in front
                    scope = kernel_scope(op_name, before[text])
                    if bare:  # forward or backward: the neighbour's mark
                        op_name = before[text]
                if scope is None:
                    seconds[UNSCOPED] += s
                    short = text.split(" = ", 1)[0]
                    unscoped[short] = unscoped.get(short, 0.0) + s
                    continue
                scoped_ops += 1
                parts = scope.split("/")
                for depth in range(1, len(parts) + 1):
                    seconds["/".join(parts[:depth])] += s
                if parts[0] == LEARNER:
                    seconds[
                        LEARNER_BWD if is_backward(op_name) else LEARNER_FWD
                    ] += s
    if not scoped_ops:
        return None
    chips = len(events)
    top = sorted(unscoped.items(), key=lambda kv: -kv[1])[:10]
    return {
        "seconds": {k: v / chips for k, v in seconds.items()},
        "total_s": total / chips,
        "unscoped_share": seconds[UNSCOPED] / total,
        "unscoped_ops": [[name, s / chips] for name, s in top],
        "events": events,
    }


def host_spans(xplane_path: str, prefix: str = SPAN_PREFIX) -> List[list]:
    """[name, start_ns, dur_ns] of the host spans whose name starts with
    ``prefix``, in start order, on the clock of the capture's device events."""
    out = []
    for plane in ProfileData.from_file(xplane_path).planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    out.append([e.name, int(e.start_ns), int(e.duration_ns)])
    return sorted(out, key=lambda r: r[1])
