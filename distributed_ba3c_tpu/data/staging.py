"""Device-ingest staging: one host copy, H2D overlapped behind the learner.

The ingest chain used to move every observation byte across the host THREE
times before a program saw it — shm-ring window → segment ``np.stack`` at
flush → collate's stack + time-major ``.copy()`` — and then ``device_put``
at the head of the step, synchronous with everything the learner was about
to do. This module is the replacement (docs/ingest.md):

- :class:`HostStagingRing`: a small ring (double-buffered by default) of
  PREALLOCATED contiguous staging arrays shaped like one collated batch.
  The feeds' in-place collates (:func:`collate_train_into` /
  :func:`collate_rollout_into`) write obs bytes from the shm-ring views
  (or block-wire frames) straight into a ring slot — ONE host copy per
  ingested block, counted by ``ingest_copies_total`` so the budget is a
  measured number, not a claim (``plane_bench --ingest`` gates it at
  exactly 1.0).
- **Donation-safety fence**: a slot whose buffers were handed to
  ``device_put`` is not writable again until every device array produced
  from it reports ready — the H2D transfer has consumed the host bytes.
  Reusing the buffer earlier would be the host-side read-after-donate
  (the J5 hazard, transfer edition); ``acquire`` pays the wait (measured:
  ``staging_wait_s`` + the ``staging_wait`` span) instead of corrupting
  an in-flight transfer. The regression test overwrites a slot right
  after the fence opens and asserts the device batch kept its bytes.
- :class:`DeviceIngest`: the async-H2D pipeline. The trainer claims batch
  k's device arrays (already dispatched), runs the step, then calls
  :meth:`DeviceIngest.prefetch` — which dispatches the H2D for batch k+1
  while the device is busy with step k. The overlap split / pod learner
  give the copy a program to hide behind; the ``h2d_copy`` span is where
  the moved cost shows up (it left the step's critical path, it did not
  disappear).
- :class:`BlockStager`: the pod learner's shape-keyed variant — reuses
  one staging TrajBlock per [T, B] shape instead of seven fresh
  ``np.ascontiguousarray`` allocations per shipped block, with the same
  ready fence and copy accounting. ``copy_in`` may run on the ingest
  receive thread (pod/ingest.py) so the wire→staging write overlaps the
  learner's step; ``to_device`` runs on the learner thread after the
  staleness gate (a rejected block cancels its slot without a transfer).

Copy accounting contract (the ``plane_bench --ingest`` measurand): the
``ingest_copies_total`` counter counts FULL PASSES over one collated
batch's obs bytes on the train-ingest path, ``ingest_blocks_total``
counts collated batches — copies-per-block is their ratio. The staged
path increments exactly 1.0 per batch (the staging write); the legacy
collates self-report their stack/transpose passes. H2D transfers are not
host copies and are never counted. The two series are ONE paired counter
(``telemetry.metrics.CounterPair``): a block's copy and the block are one
increment and a snapshot reads both totals from one read, so an epoch's
record taken while the ingest thread runs never shows one ahead of the
other.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from distributed_ba3c_tpu import telemetry

#: spec: key -> (shape, dtype) of one collated batch's arrays
Spec = Dict[str, Tuple[tuple, Any]]

#: default slot count: prefetch-queue depth (2) + one filling + one
#: in-flight transfer — enough that a healthy pipeline never waits on the
#: fence, small enough that backpressure reaches the batcher thread (the
#: shm-ring cap contract counts the feed holder, not this ring: staged
#: slots hold COPIES, never ring views)
DEFAULT_SLOTS = 4


def ingest_counts(tele_role: str = "learner"):
    """(copies, blocks) of ``tele_role``'s train-ingest path, as one pair."""
    return telemetry.registry(tele_role).counter_pair(
        "ingest_copies_total", "ingest_blocks_total")


def count_legacy_copies(
    passes: float, tele_role: str = "learner", blocks: int = 1
) -> None:
    """Self-report of a legacy (non-staged) collate: ``passes`` full
    passes over one batch's obs bytes, ``blocks`` batches (0 for an
    EXTRA pass on already-counted batches — the fleet-axis stack). ONE
    call per site — the copy budget must stay a per-batch ratio."""
    ingest_counts(tele_role).inc(passes, blocks)


class _Slot:
    """One staging slot: preallocated buffers + the fence state."""

    __slots__ = ("buffers", "handles", "index")

    def __init__(self, buffers: Dict[str, np.ndarray], index: int):
        self.buffers = buffers
        self.handles: Optional[list] = None  # device arrays from last H2D
        self.index = index


class StagedBatch(dict):
    """A collated batch living in a staging slot (dict of the slot's
    buffers, so every legacy ``batch[k]`` consumer works unchanged).
    ``trace`` rides as an attribute, never a dict key — ``device_put``
    must not meet a TraceRef. Consumers MUST resolve the slot: either
    :meth:`DeviceIngest` dispatch (which calls ``ring.dispatched``) or
    ``release()`` when the batch is abandoned."""

    def __init__(self, buffers, slot: _Slot, ring: "HostStagingRing"):
        super().__init__(buffers)
        self.slot = slot
        self.ring = ring
        self.trace = None

    def release(self) -> None:
        self.ring.release(self.slot)


def _ready(handle) -> bool:
    fn = getattr(handle, "is_ready", None)
    return fn() if fn is not None else True


_DEALIAS = None


def _dealias_fn():
    """Backend-dependent de-alias pass for staged puts.

    On TPU/GPU, ``device_put`` is a real DMA into device memory — the
    host buffer is consumed when the transfer resolves, so the ready
    fence is exactly right and this returns None (no extra pass). The
    CPU PJRT client instead ZERO-COPIES suitably-aligned numpy buffers:
    the "device" array aliases the staging slot forever, and reusing the
    slot would rewrite data a later consumer still reads (the staging
    fence test caught this live). There, the transfer is materialized as
    one device-side copy — fencing on the COPY's output is sound even
    when the put aliased, because output-ready implies the read of the
    slot finished."""
    global _DEALIAS
    if _DEALIAS is None:
        import jax

        if jax.default_backend() == "cpu":
            _DEALIAS = jax.jit(lambda x: x.copy())
        else:
            _DEALIAS = False
    return _DEALIAS or None


class HostStagingRing:
    """N preallocated staging slots with the ready fence.

    Single producer (the feed's batcher thread) acquires; a single
    consumer (the trainer / DeviceIngest) attaches device handles after
    dispatch or releases. The spec is adopted from the first ``acquire``
    — a mid-run spec change (new key set / shapes) reallocates and is
    counted (``staging_realloc_total``): batch shapes are ONE warmed
    shape per run (the audit tripwire's contract), so a nonzero realloc
    count is itself a finding.
    """

    def __init__(self, slots: int = DEFAULT_SLOTS, tele_role: str = "learner"):
        self._n = max(2, int(slots))
        self._slots: List[_Slot] = []
        self._spec: Optional[Spec] = None
        self._cursor = 0
        self._lock = threading.Lock()
        self._free = threading.Condition(self._lock)
        self._busy: set = set()  # slot indices acquired or queued, unfenced
        self.tele_role = tele_role
        tele = telemetry.registry(tele_role)
        self._c_ingest = ingest_counts(tele_role)
        self._c_waits = tele.counter("staging_waits_total")
        self._c_realloc = tele.counter("staging_realloc_total")
        self._h_wait = tele.histogram("staging_wait_s", unit=1e-6)
        # weakref-backed fn gauge (the predict/server.py idiom): the
        # process-global registry must not pin an abandoned ring's
        # preallocated buffers for the life of the process
        import weakref

        ref = weakref.ref(self)
        tele.gauge(
            "staging_slots",
            fn=lambda: len(r._slots) if (r := ref()) else 0,
        )

    # -- allocation --------------------------------------------------------
    def _alloc(self, spec: Spec) -> None:
        self._slots = [
            _Slot(
                {k: np.zeros(shape, dtype) for k, (shape, dtype) in spec.items()},
                i,
            )
            for i in range(self._n)
        ]
        self._spec = dict(spec)
        self._busy.clear()
        self._cursor = 0

    # -- producer side -----------------------------------------------------
    def acquire(
        self,
        spec: Spec,
        timeout: Optional[float] = None,
        stop: Optional[Callable[[], bool]] = None,
    ) -> Optional[_Slot]:
        """The next writable slot, or None on timeout/stop.

        Blocks (bounded) while every slot is either queued downstream or
        still being consumed by an in-flight H2D transfer — that wait IS
        the ring's backpressure, mirroring the bounded prefetch queue —
        and fences the chosen slot: its previous dispatch's device arrays
        must all report ready before the buffers are handed back."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._lock:
            if self._spec != spec:
                if self._spec is not None:
                    self._c_realloc.inc()
                self._alloc(spec)
            t0 = time.monotonic()
            waited = False
            while True:
                slot = self._next_free_locked()
                if slot is not None:
                    break
                waited = True
                remaining = 0.05
                if deadline is not None:
                    remaining = min(remaining, deadline - time.monotonic())
                    if remaining <= 0:
                        return None
                self._free.wait(remaining)
                if stop is not None and stop():
                    return None
            if waited:
                self._c_waits.inc()
            self._h_wait.observe(time.monotonic() - t0)
            self._busy.add(slot.index)
            slot.handles = None
            return slot

    def _next_free_locked(self) -> Optional[_Slot]:
        """First slot that is not downstream AND whose fence is open."""
        for off in range(len(self._slots)):
            slot = self._slots[(self._cursor + off) % len(self._slots)]
            if slot.index in self._busy:
                continue
            if slot.handles is not None and not all(
                _ready(h) for h in slot.handles
            ):
                continue  # H2D still consuming the host bytes
            self._cursor = (slot.index + 1) % len(self._slots)
            return slot
        return None

    def staged(self, slot: _Slot) -> StagedBatch:
        """Wrap an acquired (and now filled) slot for the out queue; the
        in-place collates already counted the write."""
        return StagedBatch(slot.buffers, slot, self)

    def count_staged_copy(self) -> None:
        """The ONE host copy of a staged batch (called by the in-place
        collates, once per batch)."""
        self._c_ingest.inc(1.0, 1)

    # -- consumer side -----------------------------------------------------
    def _owns(self, slot: _Slot) -> bool:
        """This slot belongs to the CURRENT ring generation. A mid-run
        spec realloc replaces the slot list; a pre-realloc StagedBatch
        resolving afterwards must not touch the new generation's
        bookkeeping — its index could name a live new slot, and freeing
        that would let the producer overwrite a queued batch's bytes."""
        return (
            slot.index < len(self._slots)
            and self._slots[slot.index] is slot
        )

    def dispatched(self, slot: _Slot, handles: list) -> None:
        """H2D dispatched for this slot: record the fence handles and put
        the slot back in rotation (writable once the transfer resolves)."""
        with self._lock:
            if not self._owns(slot):
                return  # stale pre-realloc slot: orphaned, GC owns it
            slot.handles = list(handles)
            self._busy.discard(slot.index)
            self._free.notify_all()

    def release(self, slot: _Slot) -> None:
        """Return a slot without a dispatch (shutdown / abandoned batch)."""
        with self._lock:
            if not self._owns(slot):
                return  # stale pre-realloc slot: orphaned, GC owns it
            slot.handles = None
            self._busy.discard(slot.index)
            self._free.notify_all()


# --------------------------------------------------------------------------
# specs + in-place collates (byte-exact vs data/dataflow.py collate_*)
# --------------------------------------------------------------------------


def train_spec(holder: List[list]) -> Spec:
    """Spec of ``collate_train``'s output for this holder (shapes read off
    the items — no materialization)."""
    state = holder[0][0]
    b = len(holder)
    return {
        "state": ((b, *np.shape(state)), getattr(state, "dtype", np.uint8)),
        "action": ((b,), np.int32),
        "return": ((b,), np.float32),
    }


def rollout_spec(holder: List[dict]) -> Spec:
    """Spec of ``collate_rollout``'s output (time-major [T, B] layout)."""
    seg = holder[0]
    b = len(holder)
    t = len(seg["action"])
    state = seg["state"]  # SegStates or [T, ...] ndarray — both have .shape
    boot = seg["bootstrap_state"]
    spec: Spec = {
        "state": (
            (t, b, *tuple(state.shape)[1:]),
            getattr(state, "dtype", np.uint8),
        ),
        "action": ((t, b), np.int32),
        "reward": ((t, b), np.float32),
        "done": ((t, b), np.float32),
        "behavior_log_probs": ((t, b), np.float32),
        "bootstrap_state": ((b, *np.shape(boot)), getattr(boot, "dtype", np.uint8)),
    }
    if "behavior_values" in seg:
        spec["behavior_values"] = ((t, b), np.float32)
    return spec


def _write_states(dest: np.ndarray, src) -> None:
    """One obs write: lazy sources interleave straight into ``dest``."""
    mi = getattr(src, "materialize_into", None)
    if mi is not None:
        mi(dest)
    else:
        dest[...] = src


def collate_train_into(holder: List[list], out: Dict[str, np.ndarray]) -> None:
    """In-place :func:`~distributed_ba3c_tpu.data.dataflow.collate_train`:
    byte-exact same values, written into preallocated ``out`` arrays —
    the ring-view rows' ONE copy is the staging write."""
    state_out = out["state"]
    action_out = out["action"]
    return_out = out["return"]
    for i, dp in enumerate(holder):
        _write_states(state_out[i], dp[0])
        action_out[i] = dp[1]
        return_out[i] = dp[2]


def collate_rollout_into(holder: List[dict], out: Dict[str, np.ndarray]) -> None:
    """In-place :func:`~distributed_ba3c_tpu.data.dataflow.collate_rollout`:
    same time-major [T, B] values, one obs pass — each segment's (lazy)
    state column interleaves directly into its ``out["state"][:, i]``
    stripe, never through an intermediate stack."""
    keys = ("action", "reward", "done", "behavior_log_probs")
    if "behavior_values" in holder[0]:
        keys += ("behavior_values",)
    state_out = out["state"]
    boot_out = out["bootstrap_state"]
    for i, seg in enumerate(holder):
        _write_states(state_out[:, i], seg["state"])
        _write_states(boot_out[i], seg["bootstrap_state"])
        for k in keys:
            out[k][:, i] = seg[k]


#: legacy-collate → in-place variant (the feeds' staging dispatch table)
COLLATE_INTO: Dict[str, Tuple[Callable, Callable]] = {
    "train": (train_spec, collate_train_into),
    "rollout": (rollout_spec, collate_rollout_into),
}


def acquire_stoppable(
    ring: "HostStagingRing", spec: Spec, stopped: Callable[[], bool]
) -> Optional["_Slot"]:
    """Acquire that returns None ONLY on stop — the feeds' batcher-thread
    shape (the ``queue_put_stoppable`` idiom). A transient consumer stall
    longer than any fixed timeout must pause the batcher, never kill it:
    each bounded acquire that comes back empty logs once per long stall
    (flight-recorded) and retries until the thread is told to stop."""
    stalls = 0
    while not stopped():
        slot = ring.acquire(spec, timeout=5.0, stop=stopped)
        if slot is not None:
            return slot
        stalls += 1
        if stalls == 1 or stalls % 12 == 0:  # first, then ~once a minute
            telemetry.record(
                "staging_acquire_stall",
                role=ring.tele_role,
                waited_s=5.0 * stalls,
            )
    return None


def device_put_staged(value: np.ndarray, sharding=None):
    """THE put for staged (reused) host buffers: an async transfer whose
    readiness genuinely means "the host bytes were consumed" on every
    backend (see :func:`_dealias_fn`). Fence slot reuse on ITS outputs,
    never on a raw ``device_put``'s."""
    import jax

    if jax.process_count() > 1 and sharding is not None:
        out = jax.make_array_from_process_local_data(sharding, value)
    elif sharding is not None:
        out = jax.device_put(value, sharding)
    else:
        out = jax.device_put(value)
    dealias = _dealias_fn()
    if dealias is not None:
        out = dealias(out)
    return out


# --------------------------------------------------------------------------
# the async-H2D pipeline
# --------------------------------------------------------------------------


class DeviceIngest:
    """Feed → device arrays, with the k+1 transfer hidden behind step k.

    Wraps a feed (``next_batch``/``start``/``stop``/``join``/``qsize``)
    and owns the device side of the staging contract:

    - :meth:`next_batch` returns ``{key: device_array, ["_trace"]: ref}``
      — the staged pipeline's replacement for the trainer's per-key
      ``device_put`` at the head of the step. If a prefetched batch is
      pending it is returned instantly (its H2D was dispatched behind the
      previous step); otherwise the fetch+dispatch happens now.
    - :meth:`prefetch` (call it right AFTER dispatching the learner step)
      takes whatever batch the feed has ready — non-blocking, so a quiet
      actor plane never stalls the step loop — and dispatches its H2D
      while the device executes. This is the overlap the trainer's old
      post-step staging fetch wanted but could not have (a BLOCKING fetch
      starves at shutdown; a non-blocking one cannot).

    ``sharding`` is the step's batch sharding (dict per key, or one for
    all); multi-host processes feed their local rows through
    ``make_array_from_process_local_data`` exactly like the legacy path.
    """

    is_device_ingest = True

    def __init__(self, feed, sharding, tele_role: str = "learner"):
        self.feed = feed
        self._sharding = sharding
        self._staged: Optional[Tuple[dict, Any]] = None
        self.tele_role = tele_role
        tele = telemetry.registry(tele_role)
        self._c_prefetched = tele.counter("ingest_prefetched_total")
        self._c_dispatch_now = tele.counter("ingest_dispatch_now_total")
        self._h_claim = tele.histogram("ingest_claim_s", unit=1e-6)

    # -- feed facade -------------------------------------------------------
    def start(self) -> None:
        self.feed.start()

    def stop(self) -> None:
        self.feed.stop()
        # a held prefetched batch never reaches a step: drop the
        # reference — its slot went back into rotation at dispatch (the
        # fence handles were attached there), so nothing leaks
        self._staged = None

    def join(self, timeout: Optional[float] = None) -> None:
        self.feed.join(timeout)

    def qsize(self) -> int:
        return self.feed.qsize()

    # -- device side -------------------------------------------------------
    def _put(self, key: str, value: np.ndarray):
        sh = (
            self._sharding[key]
            if isinstance(self._sharding, dict)
            else self._sharding
        )
        return device_put_staged(value, sh)

    def _dispatch(self, batch) -> Tuple[dict, Any]:
        """Issue the H2D transfers for one host batch (async); returns
        (device dict, trace)."""
        if isinstance(batch, StagedBatch):
            trace = batch.trace
            out = {k: self._put(k, v) for k, v in batch.items()}
            # fence handles: the slot becomes writable only when every
            # transfer has consumed the host bytes (donation safety)
            batch.ring.dispatched(batch.slot, list(out.values()))
        else:  # plain dict from a non-staged feed (compat path)
            trace = batch.pop("_trace", None)
            out = {k: self._put(k, v) for k, v in batch.items()}
        if trace is not None:
            trace = trace.hop("h2d_copy", self.tele_role)
        return out, trace

    def prefetch(self) -> bool:
        """Dispatch the NEXT batch's H2D if the feed has one ready now.
        Non-blocking; returns True when a batch is staged in flight."""
        if self._staged is not None:
            return True
        import queue as _queue

        try:
            batch = self.feed.next_batch(timeout=0.0)
        except _queue.Empty:
            return False
        if batch is None:
            return False
        self._staged = self._dispatch(batch)
        self._c_prefetched.inc()
        return True

    def next_batch(self, timeout: Optional[float] = None) -> dict:
        """Claim the current step's device batch (dispatching now only
        when no prefetch landed); the ``ingest`` hop of a sampled trace
        measures exactly this claim — ~0 when the H2D was hidden."""
        t0 = time.monotonic()
        if self._staged is None:
            batch = self.feed.next_batch(timeout=timeout)
            self._staged = self._dispatch(batch)
            self._c_dispatch_now.inc()
        out, trace = self._staged
        self._staged = None
        self._h_claim.observe(time.monotonic() - t0)
        if trace is not None:
            out = dict(out)
            out["_trace"] = trace.hop("ingest", self.tele_role)
        return out


# --------------------------------------------------------------------------
# the pod learner's shape-keyed block stager
# --------------------------------------------------------------------------


class StagedBlock:
    """One host-staged experience block awaiting its device transfer."""

    __slots__ = ("arrays", "slot_key", "slot_idx", "stager")

    def __init__(self, arrays: Dict[str, np.ndarray], slot_key, slot_idx, stager):
        self.arrays = arrays
        self.slot_key = slot_key
        self.slot_idx = slot_idx
        self.stager = stager


class BlockStager:
    """Reused host staging buffers for wire-fed [T, B] experience blocks.

    Replaces ``pod/learner.py``'s seven fresh ``np.ascontiguousarray``
    allocations per shipped block with ONE staging write into per-shape
    reusable buffers (the wire's frombuffer views are read exactly once),
    plus the same ready fence as :class:`HostStagingRing`. Thread
    contract: :meth:`copy_in` may run on the ingest receive thread (the
    wire→staging write then overlaps the learner's step), ``to_device``/
    ``cancel`` on the learner thread — the internal lock serializes slot
    state, never the copies themselves.
    """

    #: field dtypes of a staged block (pod/wire.py EXPERIENCE_KEYS layout)
    DTYPES = {
        "state": np.uint8,
        "action": np.int32,
        "reward": np.float32,
        "done": np.float32,
        "behavior_log_probs": np.float32,
        "behavior_values": np.float32,
        "bootstrap_state": np.uint8,
    }

    #: bounded slot wait before falling back to a transient allocation —
    #: the fence is an in-flight H2D (milliseconds); anything longer means
    #: the consumer is backed up and copy_in must NOT wedge its caller
    #: (the pod ingest's drop-oldest liveness rides on this)
    MAX_WAIT_S = 0.05

    def __init__(self, slots: int = 2, tele_role: str = "learner"):
        self._n = max(2, int(slots))
        self._lock = threading.Lock()
        # shape key -> list of [buffers dict, handles list|None, busy bool]
        self._rings: Dict[tuple, List[list]] = {}
        self._cursors: Dict[tuple, int] = {}
        self.tele_role = tele_role
        self._c_ingest = ingest_counts(tele_role)
        tele = telemetry.registry(tele_role)
        self._c_alloc = tele.counter("staging_alloc_total")
        self._c_waits = tele.counter("staging_waits_total")
        self._c_fallback = tele.counter("staging_fallback_total")

    def _slot_for(self, key: tuple, shapes: Dict[str, tuple]) -> tuple:
        deadline = time.monotonic() + self.MAX_WAIT_S
        with self._lock:
            ring = self._rings.get(key)
            if ring is None:
                ring = self._rings[key] = []
                self._cursors[key] = 0
            start = self._cursors[key]
            while True:
                fenced = False  # a non-busy slot whose H2D may resolve
                for off in range(len(ring)):
                    idx = (start + off) % len(ring)
                    bufs, handles, busy = ring[idx]
                    if busy:
                        continue
                    if handles is not None and not all(
                        _ready(h) for h in handles
                    ):
                        fenced = True
                        continue
                    ring[idx][1] = None
                    ring[idx][2] = True
                    self._cursors[key] = (idx + 1) % len(ring)
                    return bufs, idx
                if len(ring) < self._n:
                    bufs = {
                        k: np.zeros(shapes[k], self.DTYPES[k])
                        for k in shapes
                    }
                    ring.append([bufs, None, True])
                    self._c_alloc.inc()
                    return bufs, len(ring) - 1
                if not fenced or time.monotonic() >= deadline:
                    # a transient (non-ring) allocation keeps the caller
                    # live, counted so a starved ring is visible. `not
                    # fenced` short-circuits: every slot is HELD
                    # DOWNSTREAM (unconsumed staged blocks — the
                    # backlogged regime drop-oldest exists for), so no
                    # amount of waiting here frees one; only an in-flight
                    # H2D (fenced) is worth the bounded poll
                    self._c_fallback.inc()
                    return (
                        {k: np.zeros(shapes[k], self.DTYPES[k]) for k in shapes},
                        None,
                    )
                # bounded wait, then re-scan (fence = in-flight H2D)
                self._c_waits.inc()
                self._free_wait()  # ba3cflow: disable=F1 — _free_wait drops self._lock around its sleep (see its body)

    def _free_wait(self) -> None:
        # called with the lock held: drop it for the sleep so to_device/
        # cancel can flip slot state
        self._lock.release()
        try:
            time.sleep(0.001)
        finally:
            self._lock.acquire()

    def copy_in(self, batch: Dict[str, np.ndarray]) -> StagedBlock:
        """The one host copy: wire views → this shape's staging buffers.
        Dtype coercion happens here (the program's input contract), same
        as the legacy ``batch_to_block``."""
        t, b = np.shape(batch["action"])
        shapes = {
            "state": np.shape(batch["state"]),
            "action": (t, b),
            "reward": (t, b),
            "done": (t, b),
            "behavior_log_probs": (t, b),
            "behavior_values": (t, b),
            "bootstrap_state": np.shape(batch["bootstrap_state"]),
        }
        key = (shapes["state"], shapes["bootstrap_state"])
        bufs, idx = self._slot_for(key, shapes)
        for k, dst in bufs.items():
            np.copyto(dst, batch[k], casting="unsafe")
        self._c_ingest.inc(1.0, 1)
        return StagedBlock(bufs, key, idx, self)

    def to_device(self, staged: StagedBlock, block_sharding=None):
        """Staged host block → device TrajBlock (async H2D); the slot's
        fence closes on the transfer handles."""
        import jax

        from distributed_ba3c_tpu.fused.overlap import TrajBlock

        a = staged.arrays
        leaves = TrajBlock(
            states=a["state"],
            actions=a["action"],
            rewards=a["reward"],
            dones=a["done"],
            behavior_log_probs=a["behavior_log_probs"],
            behavior_values=a["behavior_values"],
            bootstrap_state=a["bootstrap_state"],
        )
        if block_sharding is None:
            block = jax.tree_util.tree_map(jax.device_put, leaves)
        else:
            block = jax.tree_util.tree_map(
                jax.device_put, leaves, block_sharding
            )
        dealias = _dealias_fn()
        if dealias is not None:
            block = jax.tree_util.tree_map(dealias, block)
        if staged.slot_idx is not None:
            with self._lock:
                slot = self._rings[staged.slot_key][staged.slot_idx]
                slot[1] = list(jax.tree_util.tree_leaves(block))
                slot[2] = False
        return block

    def cancel(self, staged: StagedBlock) -> None:
        """A gate-rejected block frees its slot without a transfer (no-op
        for transient fallback allocations)."""
        if staged.slot_idx is None:
            return
        with self._lock:
            slot = self._rings[staged.slot_key][staged.slot_idx]
            slot[1] = None
            slot[2] = False
