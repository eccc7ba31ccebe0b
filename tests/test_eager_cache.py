"""Eager compiled-program cache tests (SURVEY §7 M1; VERDICT r01 item 4).

The dispatch path compiles one XLA executable per (op, shapes, dtypes, attrs)
key and reuses it, including the vjp path: repeated eager dispatch on one key
hits the cache, retraces nothing and lowers nothing (counts, not timings).
"""
import numpy as np

import paddle_tpu as paddle
from paddle_tpu.core import flags
from paddle_tpu.ops import api, registry


def test_cache_populates_and_hits():
    registry._EXEC_CACHE.clear()
    x = paddle.to_tensor(np.random.randn(4, 8).astype(np.float32))
    y = paddle.to_tensor(np.random.randn(8, 2).astype(np.float32))
    api.matmul(x, y)
    n1 = len(registry._EXEC_CACHE)
    assert n1 >= 1
    api.matmul(x, y)  # same key: no new entry
    assert len(registry._EXEC_CACHE) == n1
    z = paddle.to_tensor(np.random.randn(2, 2).astype(np.float32))
    api.matmul(z, z)  # new shapes: new entry
    assert len(registry._EXEC_CACHE) == n1 + 1


def test_cached_results_match_uncached():
    x = paddle.to_tensor(np.random.randn(6, 6).astype(np.float32),
                         stop_gradient=False)
    y = paddle.to_tensor(np.random.randn(6, 6).astype(np.float32),
                         stop_gradient=False)
    out = api.matmul(x, y)
    out.sum().backward()
    gx, gy = np.asarray(x.grad._value), np.asarray(y.grad._value)

    x._grad = y._grad = None
    flags.set_flags({"eager_op_cache": False})
    try:
        out2 = api.matmul(x, y)
        out2.sum().backward()
    finally:
        flags.set_flags({"eager_op_cache": True})
    np.testing.assert_allclose(np.asarray(out._value), np.asarray(out2._value),
                               rtol=1e-6)
    np.testing.assert_allclose(gx, np.asarray(x.grad._value), rtol=1e-6)
    np.testing.assert_allclose(gy, np.asarray(y.grad._value), rtol=1e-6)


def test_rng_ops_not_cached_and_still_random():
    x = paddle.to_tensor(np.ones((64,), np.float32))
    a = api.dropout(x, p=0.5, training=True)
    b = api.dropout(x, p=0.5, training=True)
    assert not np.array_equal(np.asarray(a._value), np.asarray(b._value))


def test_dynamic_shape_op_falls_back():
    x = paddle.to_tensor(np.array([0.0, 1.0, 0.0, 2.0], np.float32))
    out = api.nonzero(x)  # data-dependent output shape
    assert np.asarray(out._value if hasattr(out, "_value") else out[0]._value).size >= 2
    # second call goes through the fallback set without error
    api.nonzero(x)


def test_repeated_dispatch_hits_the_cache_and_never_retraces():
    """What the cache is for, as counts (the ratio of two CPU timings that
    stood here says nothing a chip's user pays for, and failed on a busy
    box): 100 dispatches on one key add no cache entry, the entry stays
    the same executable, its jit traces once, and jax lowers nothing."""
    import jax.monitoring

    lowerings = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _secs, **_kw: lowerings.append(event)
        if event.endswith("jaxpr_to_mlir_module_duration") else None)
    registry._EXEC_CACHE.clear()
    x = paddle.to_tensor(np.random.randn(256, 256).astype(np.float32))
    y = paddle.to_tensor(np.random.randn(256, 256).astype(np.float32))
    api.matmul(x, y)  # builds the entry and compiles it
    assert lowerings, "the first dispatch lowers a program"
    entries = dict(registry._EXEC_CACHE)
    execs = [f for e in entries.values() for f in e[:2] if f is not None]
    traced = [f._cache_size() for f in execs]
    assert sum(traced) >= 1
    lowered = len(lowerings)

    for _ in range(100):
        out = api.matmul(x, y)
    out._value.block_until_ready()

    assert dict(registry._EXEC_CACHE) == entries      # same keys, same entries
    assert [f._cache_size() for f in execs] == traced  # no retrace
    assert len(lowerings) == lowered                   # nothing lowered
    np.testing.assert_allclose(np.asarray(out._value),
                               np.asarray(x._value) @ np.asarray(y._value),
                               rtol=1e-4, atol=1e-4)


def test_exec_cache_lru_bound():
    """FLAGS_eager_op_cache_size bounds the executable cache with LRU
    eviction (reference: size-bounded autotune cache, phi autotune/cache.h)."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.ops import registry

    old = paddle.get_flags("eager_op_cache_size")["eager_op_cache_size"]
    with registry._CACHE_LOCK:
        registry._EXEC_CACHE.clear()
    paddle.set_flags({"eager_op_cache_size": 4})
    try:
        for n in range(1, 8):  # 7 distinct shape keys
            x = paddle.to_tensor(np.ones((n,), np.float32))
            (x + x).numpy()
        assert len(registry._EXEC_CACHE) <= 4
        # most-recent key stays cached across a new insert; oldest evicted
        keys_before = list(registry._EXEC_CACHE)
        x = paddle.to_tensor(np.ones((9,), np.float32))
        (x + x).numpy()
        keys_after = list(registry._EXEC_CACHE)
        assert keys_before[-1] in keys_after
        assert keys_before[0] not in keys_after
    finally:
        paddle.set_flags({"eager_op_cache_size": old})
