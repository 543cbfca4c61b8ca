"""BatchedPredictor: batching, padding buckets, async callbacks, param swap."""

import threading

import jax
import numpy as np

from distributed_ba3c_tpu.config import BA3CConfig
from distributed_ba3c_tpu.models.a3c import BA3CNet
from distributed_ba3c_tpu.predict.server import BatchedPredictor, _next_pow2


def _make(greedy=False, num_threads=1):
    cfg = BA3CConfig(image_size=(16, 16), fc_units=16, num_actions=4)
    model = BA3CNet(num_actions=cfg.num_actions, fc_units=cfg.fc_units)
    params = model.init(jax.random.PRNGKey(0), np.zeros((1, *cfg.state_shape), np.uint8))[
        "params"
    ]
    pred = BatchedPredictor(
        model, params, batch_size=8, num_threads=num_threads, greedy=greedy
    )
    return cfg, model, pred


def test_next_pow2():
    assert [_next_pow2(n) for n in (1, 2, 3, 5, 8, 9)] == [1, 2, 4, 8, 8, 16]


def test_sync_predict_shapes_and_padding():
    cfg, _, pred = _make()
    states = np.zeros((5, *cfg.state_shape), np.uint8)  # pads to 8
    actions, values, greedy = pred.predict_batch(states)
    assert actions.shape == (5,) and values.shape == (5,)
    assert greedy.shape == (5,)
    assert ((actions >= 0) & (actions < cfg.num_actions)).all()
    assert ((greedy >= 0) & (greedy < cfg.num_actions)).all()


def test_greedy_matches_argmax():
    cfg, model, pred = _make(greedy=True)
    rng = np.random.default_rng(0)
    states = rng.integers(0, 255, (4, *cfg.state_shape), np.uint8)
    actions, _, greedy = pred.predict_batch(states)
    # with greedy=True the serving actions ARE the argmax channel
    np.testing.assert_array_equal(actions, greedy)


def test_async_callbacks_all_fire():
    cfg, _, pred = _make(num_threads=2)
    pred.start()
    try:
        n = 100
        done = threading.Event()
        results = {}
        lock = threading.Lock()
        rng = np.random.default_rng(1)

        def make_cb(i):
            def cb(action, value, logp):
                with lock:
                    results[i] = (action, value, logp)
                    if len(results) == n:
                        done.set()

            return cb

        for i in range(n):
            pred.put_task(
                rng.integers(0, 255, cfg.state_shape, np.uint8), make_cb(i)
            )
        assert done.wait(timeout=60), f"only {len(results)}/{n} callbacks fired"
        for a, v, lp in results.values():
            assert 0 <= a < cfg.num_actions
            assert np.isfinite(v)
            assert lp <= 0.0  # a log-probability
    finally:
        pred.stop()


def test_update_params_changes_output():
    cfg, model, pred = _make(greedy=True)
    states = np.full((2, *cfg.state_shape), 128, np.uint8)
    _, values_before, _ = pred.predict_batch(states)
    new_params = model.init(
        jax.random.PRNGKey(7), np.zeros((1, *cfg.state_shape), np.uint8)
    )["params"]
    pred.update_params(new_params)
    _, values_after, _ = pred.predict_batch(states)
    assert not np.allclose(values_before, values_after)


# -- the null-device fake (predict/null.py) -----------------------------------


def _null(service_s=0.0, **kw):
    from types import SimpleNamespace

    from distributed_ba3c_tpu.predict.null import make_null_predictor

    # a stub model is enough: the null device never traces the forward
    model = SimpleNamespace(num_actions=4, apply=None)
    return make_null_predictor(
        model, {}, 4, service_s=service_s, coalesce_ms=0.0, **kw
    )


def test_null_predictor_rows_in_actions_out():
    """Sync and queued paths both answer one in-range action per row."""
    pred = _null(batch_size=8)
    actions, values, greedy = pred.predict_batch(np.zeros((5, 16, 16, 4), np.uint8))
    assert actions.shape == values.shape == greedy.shape == (5,)
    assert ((actions >= 0) & (actions < 4)).all()
    pred.start()
    try:
        got, done = [], threading.Event()

        def cb(action, value, logp):
            got.append(int(action))
            if len(got) == 20:
                done.set()

        for _ in range(20):
            pred.put_task(np.zeros((16, 16, 4), np.uint8), cb)
        assert done.wait(20.0), f"only {len(got)}/20 callbacks fired"
        assert all(0 <= a < 4 for a in got)
    finally:
        pred.stop()
        pred.join(timeout=5)


def test_null_predictor_honours_service_time():
    """``service_s`` is paid once a device call, at fetch."""
    import time

    pred = _null(service_s=0.05, batch_size=8)
    t0 = time.monotonic()
    pred.predict_batch(np.zeros((8, 16, 16, 4), np.uint8))
    assert time.monotonic() - t0 >= 0.05


def test_null_predictor_stops():
    pred = _null(batch_size=8)
    pred.start()
    pred.stop()
    pred.join(timeout=5)
    assert not any(t.is_alive() for t in pred.threads)
    # a stopped predictor admits nothing: the task comes back unserved
    assert pred.put_task(np.zeros((16, 16, 4), np.uint8), lambda *a: None) is False
