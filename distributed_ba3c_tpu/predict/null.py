"""A fake of :class:`BatchedPredictor` for tests and the device-free plane
instruments: the scheduler is the real one, the device is host numpy."""

from __future__ import annotations

import threading
import time

import numpy as np

from distributed_ba3c_tpu.predict.server import BatchedPredictor


def make_null_predictor(model, params, n_actions: int, service_s: float = 0.0,
                        **kw):
    """A BatchedPredictor whose 'device' is host numpy: identical queueing,
    continuous-batching scheduler, deadline/shed machinery and callbacks —
    only the dispatch/fetch pair is replaced by thread-safe host-side
    random actions. The plane's own ceiling measurement
    (scripts/plane_bench.py) uses this to take the device out of the loop.

    ``service_s`` > 0 simulates a device that takes that long PER CALL
    (slept at fetch time, like a real serialized device queue) — the knob
    ``scripts/serving_bench.py`` uses to give the latency frontier a real
    service-time axis on a device-free host."""

    class _NullDevicePredictor(BatchedPredictor):
        """Identical scheduler machinery; the 'device' is host numpy."""

        def __init__(self, *a, **kws):
            super().__init__(*a, **kws)
            self._null_rng = np.random.default_rng(0)
            # numpy Generators are not thread-safe and the sync
            # predict_batch path can race the scheduler thread here (the
            # real predictor guards its PRNG key with a lock — keep the
            # invariant)
            self._null_lock = threading.Lock()

        def _dispatch(self, params, batch, fwd=None):
            # 'dispatch' computes eagerly on host; 'fetch' pays the
            # simulated device time, so the depth-2 pipeline sees the
            # same serialized-device timing a real backend gives it
            k = np.asarray(batch).shape[0]
            with self._null_lock:
                acts = self._null_rng.integers(0, n_actions, k).astype(
                    np.int32
                )
            vals = np.zeros(k, np.float32)
            logp = np.full(k, -np.log(n_actions), np.float32)
            return k, (acts, vals, logp, acts)

        def _collect(self, handle):
            if service_s > 0:
                time.sleep(service_s)  # ba3clint: disable=A9 — the sleep IS the simulated device's service time
            return handle[1]

    return _NullDevicePredictor(model, params, **kw)
