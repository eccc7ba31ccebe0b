"""PR-2 step-time optimization layer: prefetch overlap, bucketed all-reduce
parity, autotune persistence, async checkpoints, AOT dispatch, compile cache.
"""
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer
from paddle_tpu.core import autotune, flags
from paddle_tpu.distributed import grad_buckets  # noqa: F401  (defines flags)
from paddle_tpu.io import prefetch  # noqa: F401  (defines flags)
from paddle_tpu.jit import compile_cache  # noqa: F401  (defines flags)
from paddle_tpu.jit.trainer import TrainStep


@pytest.fixture
def mesh8():
    return Mesh(np.array(jax.devices()), ("dp",))


@pytest.fixture(autouse=True)
def _restore_flags():
    keep = {k: flags.get_flag(k) for k in (
        "use_autotune", "autotune_cache_dir", "jit_fast_dispatch",
        "io_device_prefetch", "io_prefetch_depth", "grad_bucket_mb")}
    yield
    flags.set_flags(keep)
    autotune.clear_cache()


# ---------------------------------------------------------------- prefetcher
class TestDevicePrefetcher:
    def _gen(self, n, produced=None, fail_at=None, delay=0.0):
        for i in range(n):
            if fail_at is not None and i == fail_at:
                raise RuntimeError("loader died")
            if delay:
                time.sleep(delay)
            if produced is not None:
                produced.append(i)
            yield {"x": np.full((2, 2), i, np.float32), "i": i}

    def test_ordering_and_device_placement(self):
        from paddle_tpu.io import DevicePrefetcher

        with DevicePrefetcher(self._gen(8), depth=2) as pf:
            out = list(pf)
        assert [b["i"] for b in out] == list(range(8))
        assert all(isinstance(b["x"], jax.Array) for b in out)
        assert pf.stats["batches"] == 8

    def test_tensor_leaves_stay_tensors(self):
        from paddle_tpu.io import DevicePrefetcher

        batch = {"t": paddle.to_tensor([1.0, 2.0]), "a": np.zeros(3)}
        got = next(DevicePrefetcher(iter([batch]), depth=1))
        assert isinstance(got["t"], paddle.Tensor)
        assert isinstance(got["a"], jax.Array)

    def test_boundedness(self):
        from paddle_tpu.io import DevicePrefetcher

        produced = []
        pf = DevicePrefetcher(self._gen(50, produced=produced), depth=2)
        time.sleep(0.5)  # consumer never pulls
        # queue holds `depth`; at most one more is in flight in _put
        assert len(produced) <= 3
        pf.close()

    def test_exception_after_prior_batches(self):
        from paddle_tpu.io import DevicePrefetcher

        pf = DevicePrefetcher(self._gen(6, fail_at=3), depth=2)
        got = []
        with pytest.raises(RuntimeError, match="loader died"):
            for b in pf:
                got.append(b["i"])
        assert got == [0, 1, 2]  # everything produced before the error

    def test_sharded_placement(self, mesh8):
        from paddle_tpu.io import DevicePrefetcher

        sharding = NamedSharding(mesh8, P("dp"))
        batch = next(DevicePrefetcher(
            iter([np.zeros((16, 4), np.float32)]), depth=1,
            sharding=sharding))
        assert batch.sharding == sharding

    def test_maybe_prefetch_flag_gate(self):
        from paddle_tpu.io import DevicePrefetcher, maybe_prefetch

        src = [np.zeros(2)]
        assert maybe_prefetch(src) is src
        flags.set_flags({"io_device_prefetch": True})
        wrapped = maybe_prefetch(iter(src))
        assert isinstance(wrapped, DevicePrefetcher)
        wrapped.close()

    def test_close_idempotent(self):
        from paddle_tpu.io import DevicePrefetcher

        pf = DevicePrefetcher(self._gen(4), depth=1)
        next(pf)
        pf.close()
        pf.close()


# ----------------------------------------------------- bucketed all-reduce
class TestBucketedAllReduce:
    def test_partition_reverse_contiguous(self):
        from paddle_tpu.distributed.grad_buckets import partition_buckets

        shapes = [(4,), (4,), (4,), (4,)]
        dtypes = [jnp.float32] * 4
        # 8 bytes/bucket = two fp32[4] never fit together -> one each,
        # reverse order
        assert partition_buckets(shapes, dtypes, 16) == [[3], [2], [1], [0]]
        # 32 bytes fits two
        assert partition_buckets(shapes, dtypes, 32) == [[3, 2], [1, 0]]
        # everything
        assert partition_buckets(shapes, dtypes, 1 << 62) == [[3, 2, 1, 0]]

    def test_partition_dtype_uniform_and_oversized(self):
        from paddle_tpu.distributed.grad_buckets import partition_buckets

        shapes = [(2,), (2,), (100,)]
        dtypes = [jnp.float32, jnp.int32, jnp.float32]
        parts = partition_buckets(shapes, dtypes, 1 << 20)
        # oversized-vs-budget never splits a tensor; dtype boundary splits
        for bucket in parts:
            assert len({str(dtypes[i]) for i in bucket}) == 1
        assert sorted(i for b in parts for i in b) == [0, 1, 2]

    def test_bucket_reduce_matches_single_allreduce(self, mesh8):
        """Bucketed pmean is bitwise identical to one coalesced pmean."""
        from jax import shard_map
        from paddle_tpu.distributed.grad_buckets import bucket_reduce

        rng = np.random.RandomState(0)
        gs = [rng.rand(8, 3).astype(np.float32),
              rng.rand(8, 7).astype(np.float32),
              rng.rand(8, 5).astype(np.float32)]

        def reduced(bucket_bytes):
            def f(*g):
                return tuple(bucket_reduce(list(g), "dp", bucket_bytes))

            fn = shard_map(f, mesh=mesh8, in_specs=(P("dp"),) * 3,
                           out_specs=(P(),) * 3,
                           axis_names=frozenset({"dp"}), check_vma=False)
            return jax.jit(fn)(*gs)

        single = reduced(1 << 62)
        tiny = reduced(16)   # every tensor its own bucket
        small = reduced(64)  # mixed coalescing
        for a, b, c in zip(single, tiny, small):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
            np.testing.assert_allclose(np.asarray(a), np.asarray(c), atol=1e-6)

    def _linear_losses(self, mesh8, **kw):
        paddle.seed(3)
        model = nn.Linear(4, 2)
        loss_fn = nn.CrossEntropyLoss()
        opt = optimizer.SGD(0.1, parameters=model.parameters())
        step = TrainStep(model, lambda a, b: loss_fn(model(a), b), opt, **kw)
        x = np.random.RandomState(0).randn(16, 4).astype(np.float32)
        y = np.random.RandomState(1).randint(0, 2, 16)
        losses = [float(step(paddle.to_tensor(x),
                             paddle.to_tensor(y)).item()) for _ in range(3)]
        return losses, [p.numpy().copy() for p in model.parameters()]

    def test_trainstep_dp_axis_matches_gspmd(self, mesh8):
        ref_losses, ref_params = self._linear_losses(mesh8)
        for mb in (-1, 0, 4):
            losses, params = self._linear_losses(
                mesh8, mesh=mesh8, dp_axis="dp", grad_bucket_mb=mb)
            np.testing.assert_allclose(losses, ref_losses, atol=1e-6)
            for p, r in zip(params, ref_params):
                np.testing.assert_allclose(p, r, atol=1e-6)

    def test_trainstep_dp_axis_rejects_conflicts(self, mesh8):
        paddle.seed(0)
        model = nn.Linear(2, 2)
        opt = optimizer.SGD(0.1, parameters=model.parameters())
        with pytest.raises(ValueError, match="not an axis of the active mesh"):
            TrainStep(model, lambda a: model(a).sum(), opt, dp_axis="nope",
                      mesh=mesh8)
        with pytest.raises(ValueError, match="in_shardings"):
            TrainStep(model, lambda a: model(a).sum(), opt, dp_axis="dp",
                      mesh=mesh8, in_shardings=(None,) * 6)

    def test_fleet_dp_train_step_knob(self, mesh8):
        from paddle_tpu.distributed.fleet import (DistributedStrategy,
                                                  dp_train_step)

        strategy = DistributedStrategy()
        strategy.dp_comm_configs["bucketed_allreduce"] = True
        strategy.dp_comm_configs["grad_bucket_mb"] = 2
        paddle.seed(0)
        model = nn.Linear(4, 2)
        opt = optimizer.SGD(0.1, parameters=model.parameters())
        step = dp_train_step(model, lambda a: model(a).sum(), opt,
                             strategy=strategy, mesh=mesh8)
        assert step._dp_axis == "dp"
        assert step._bucket_bytes == 2 << 20
        off = DistributedStrategy()
        off.dp_comm_configs["bucketed_allreduce"] = False
        paddle.seed(0)
        model2 = nn.Linear(4, 2)
        opt2 = optimizer.SGD(0.1, parameters=model2.parameters())
        step2 = dp_train_step(model2, lambda a: model2(a).sum(), opt2,
                              strategy=off, mesh=mesh8)
        assert step2._bucket_bytes == 1 << 62  # single all-reduce


# --------------------------------------------------------- autotune cache
class TestAutotuneCache:
    def _tuned(self, calls):
        @autotune.autotune([{"b": 2}, {"b": 4}])
        def f(x, b=2):
            calls.append(b)
            return x * b

        return f

    def test_hit_miss_counters_and_persistence(self, tmp_path):
        calls = []
        f = self._tuned(calls)
        flags.set_flags({"use_autotune": True,
                         "autotune_cache_dir": str(tmp_path)})
        x = jnp.ones((4,))
        f(x)
        info = autotune.cache_info()
        assert info["misses"] == 1 and info["tunes"] == 1
        f(x)
        assert autotune.cache_info()["hits"] == 1
        cache_file = tmp_path / "autotune_cache.json"
        assert cache_file.exists()
        stored = json.loads(cache_file.read_text())
        assert all(v in ({"b": 2}, {"b": 4}) for v in stored.values())

        # "restart": in-memory cache gone, disk winner reused without tuning
        autotune.clear_cache()
        flags.set_flags({"use_autotune": True,
                         "autotune_cache_dir": str(tmp_path)})
        calls.clear()
        f(x)
        info = autotune.cache_info()
        assert info["disk_hits"] == 1 and info["tunes"] == 0
        assert len(calls) == 1  # ran once with the winner, no re-timing

    def test_corrupt_cache_falls_back_to_tuning(self, tmp_path):
        calls = []
        f = self._tuned(calls)
        cache_file = tmp_path / "autotune_cache.json"
        cache_file.write_text("{definitely not json")
        flags.set_flags({"use_autotune": True,
                         "autotune_cache_dir": str(tmp_path)})
        f(jnp.ones((4,)))
        info = autotune.cache_info()
        assert info["disk_errors"] >= 1 and info["tunes"] == 1
        # the re-tune rewrote a valid file
        json.loads(cache_file.read_text())

    def test_unknown_disk_config_rejected(self, tmp_path):
        calls = []
        f = self._tuned(calls)
        flags.set_flags({"use_autotune": True,
                         "autotune_cache_dir": str(tmp_path)})
        x = jnp.ones((4,))
        f(x)
        cache_file = tmp_path / "autotune_cache.json"
        poisoned = {k: {"b": 999}
                    for k in json.loads(cache_file.read_text())}
        cache_file.write_text(json.dumps(poisoned))
        autotune.clear_cache()
        flags.set_flags({"use_autotune": True,
                         "autotune_cache_dir": str(tmp_path)})
        f(x)
        info = autotune.cache_info()
        assert info["disk_hits"] == 0 and info["tunes"] == 1
        assert 999 not in calls

    def test_backend_in_key(self, tmp_path):
        calls = []
        f = self._tuned(calls)
        flags.set_flags({"use_autotune": True,
                         "autotune_cache_dir": str(tmp_path)})
        f(jnp.ones((4,)))
        stored = json.loads((tmp_path / "autotune_cache.json").read_text())
        assert all("'cpu'" in k for k in stored)


# ------------------------------------------------------- async checkpoint
class TestAsyncCheckpoint:
    def test_snapshot_isolated_from_caller_mutation(self, tmp_path):
        from paddle_tpu.resilience.checkpoint_manager import CheckpointManager

        m = CheckpointManager(str(tmp_path), async_save=True)
        w = np.arange(6, dtype=np.float32)
        m.save(1, {"w": w})
        w[:] = -1  # after save() returns, the snapshot must be frozen
        m.wait()
        got = m.restore_latest().state["w"]
        np.testing.assert_array_equal(np.asarray(got),
                                      np.arange(6, dtype=np.float32))

    def test_ordered_commits_without_explicit_wait(self, tmp_path):
        from paddle_tpu.resilience.checkpoint_manager import CheckpointManager

        m = CheckpointManager(str(tmp_path), async_save=True)
        for s in (1, 2, 3):
            m.save(s, {"w": np.full(4, float(s), np.float32)})
        r = m.restore_latest()  # implies wait()
        assert r.step == 3
        np.testing.assert_array_equal(np.asarray(r.state["w"]),
                                      np.full(4, 3.0, np.float32))

    def test_async_error_surfaces_and_previous_survives(self, tmp_path):
        from paddle_tpu.resilience import chaos
        from paddle_tpu.resilience.checkpoint_manager import CheckpointManager

        m = CheckpointManager(str(tmp_path), async_save=True)
        m.save(1, {"w": np.ones(3, np.float32)})
        m.wait()
        chaos.inject_crash("ckpt.before_commit")
        try:
            m.save(2, {"w": np.zeros(3, np.float32)})
            with pytest.raises(chaos.InjectedCrash):
                m.wait()
        finally:
            chaos.clear()
        assert m.restore_latest().step == 1

    def test_trainer_run_waits_for_final_commit(self, tmp_path):
        from paddle_tpu.resilience import CheckpointManager, ResilientTrainer

        paddle.seed(0)
        model = nn.Linear(4, 2)
        loss_fn = nn.CrossEntropyLoss()
        opt = optimizer.SGD(0.1, parameters=model.parameters())
        mgr = CheckpointManager(str(tmp_path), async_save=True)
        trainer = ResilientTrainer(
            model, lambda a, b: loss_fn(model(a), b), opt, mgr,
            save_every=0, nan_guard=False)
        x = paddle.to_tensor(np.random.RandomState(0).randn(8, 4)
                             .astype(np.float32))
        y = paddle.to_tensor(np.random.RandomState(1).randint(0, 2, 8))
        report = trainer.run([(x, y)] * 3, epochs=1, resume=False)
        assert report["status"] == "completed"
        # run() returned -> the final async save is already committed
        assert mgr._thread is None
        assert mgr.restore_latest() is not None


# ------------------------------------------------ AOT dispatch + compile cache
class TestFastDispatch:
    def _build(self):
        paddle.seed(5)
        model = nn.Linear(4, 3)
        loss_fn = nn.CrossEntropyLoss()
        opt = optimizer.SGD(0.1, parameters=model.parameters())
        return model, TrainStep(model, lambda a, b: loss_fn(model(a), b), opt)

    def test_aot_matches_jit(self):
        x = np.random.RandomState(0).randn(8, 4).astype(np.float32)
        y = np.random.RandomState(1).randint(0, 3, 8)
        _, s1 = self._build()
        ref = [float(s1(paddle.to_tensor(x), paddle.to_tensor(y)).item())
               for _ in range(3)]
        flags.set_flags({"jit_fast_dispatch": True})
        _, s2 = self._build()
        got = [float(s2(paddle.to_tensor(x), paddle.to_tensor(y)).item())
               for _ in range(3)]
        assert s2._aot is not None
        np.testing.assert_allclose(got, ref, rtol=0, atol=0)

    def test_signature_change_recompiles(self):
        flags.set_flags({"jit_fast_dispatch": True})
        _, step = self._build()
        x8 = np.random.RandomState(0).randn(8, 4).astype(np.float32)
        y8 = np.random.RandomState(1).randint(0, 3, 8)
        float(step(paddle.to_tensor(x8), paddle.to_tensor(y8)).item())
        first = step._aot
        x4, y4 = x8[:4], y8[:4]
        float(step(paddle.to_tensor(x4), paddle.to_tensor(y4)).item())
        assert step._aot is not first  # new executable for the new shape


class TestCompileCache:
    def test_entries_written(self, tmp_path, monkeypatch):
        from paddle_tpu.jit import compile_cache

        # an explicit directory is honoured only when the environment does
        # not place the cache (tests/test_chip_smoke.py holds that side)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        d = compile_cache.enable_persistent_cache(str(tmp_path / "xla"))
        try:
            jax.jit(lambda v: v * 3.5 + 1)(jnp.ones((32, 32))
                                           ).block_until_ready()
            assert os.listdir(d), "no compilation cache entries written"
            assert compile_cache.cache_dir() == d
        finally:
            jax.config.update("jax_compilation_cache_dir", None)
            compile_cache._enabled_dir = None
