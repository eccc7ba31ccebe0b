"""spawn, multiprocessing tensor sharing, TensorArray, SelectedRows
(reference: distributed/spawn.py:428, incubate/multiprocessing/reductions.py,
python/paddle/tensor/array.py, phi selected_rows)."""
import functools

import numpy as np
import pytest

import paddle_tpu as paddle


def _rank_fn(scale):
    import os

    import numpy as np

    import paddle_tpu as paddle

    rank = int(os.environ["PADDLE_TRAINER_ID"])
    n = int(os.environ["PADDLE_TRAINERS_NUM"])
    t = paddle.to_tensor(np.full((4,), float(rank) * scale, np.float32))
    return rank, n, t


def _boom():
    raise ValueError("rank exploded")


class TestSpawn:
    def test_spawn_returns_per_rank_results(self):
        import paddle_tpu.distributed as dist

        results = dist.spawn(_rank_fn, args=(2.0,), nprocs=3)
        assert len(results) == 3
        for rank, (r, n, t) in enumerate(results):
            assert r == rank and n == 3
            np.testing.assert_allclose(np.asarray(t._value), rank * 2.0)

    def test_spawn_propagates_errors(self):
        import paddle_tpu.distributed as dist

        with pytest.raises(RuntimeError, match="rank exploded"):
            dist.spawn(_boom, nprocs=2)

    def test_spawn_join_false(self):
        import paddle_tpu.distributed as dist

        ctx = dist.spawn(_rank_fn, args=(1.0,), nprocs=2, join=False)
        assert len(ctx.processes) == 2
        out = ctx.join()
        assert sorted(r for r, _, _ in out) == [0, 1]


class TestMultiprocessingTensors:
    def test_forking_pickler_roundtrip(self):
        """The mp-queue wire format: ForkingPickler bytes with the reducers
        registered. Exercised in-process — exactly the bytes a queue would
        carry — because real mp children under pytest re-execute the test
        session (spawn main-module fixup) or risk fork-after-jax deadlocks."""
        import io
        import pickle as _pickle
        from multiprocessing.reduction import ForkingPickler

        import paddle_tpu.multiprocessing as mp  # noqa: F401 — registers reducers
        from paddle_tpu.nn.layer import Parameter

        x = paddle.to_tensor(
            np.random.RandomState(0).randn(16, 16).astype(np.float32))
        p = Parameter(np.ones((3, 3), np.float32))
        p.name = "w0"
        for obj, cls in ((x, paddle.Tensor), (p, Parameter)):
            buf = io.BytesIO()
            ForkingPickler(buf).dump(obj)
            out = _pickle.loads(buf.getvalue())
            assert type(out) is cls
            np.testing.assert_allclose(np.asarray(out._value),
                                       np.asarray(obj._value), rtol=1e-6)
        assert _pickle.loads(ForkingPickler.dumps(p)).name == "w0"

    def test_plain_pickle_tensor(self):
        import pickle as _pickle

        x = paddle.to_tensor(np.arange(6.0, dtype=np.float32),
                             stop_gradient=False)
        y = _pickle.loads(_pickle.dumps(x))
        assert isinstance(y, paddle.Tensor) and y.stop_gradient is False
        np.testing.assert_allclose(np.asarray(y._value),
                                   np.asarray(x._value))

    def test_deepcopy_preserves_parameter(self):
        """Regression: __reduce__ must keep the Parameter subclass and
        trainable metadata — nn.Transformer deepcopies layers and the
        optimizer filters on p.trainable."""
        import copy

        from paddle_tpu import nn, optimizer

        layer = nn.Linear(4, 4)
        clone = copy.deepcopy(layer)
        for p in clone.parameters():
            assert type(p).__name__ == "Parameter"
            assert p.trainable and not p.stop_gradient
        opt = optimizer.SGD(0.1, parameters=clone.parameters())
        x = paddle.to_tensor(np.ones((2, 4), np.float32))
        loss = (clone(x) ** 2).sum()
        loss.backward()
        before = np.asarray(clone.weight._value).copy()
        opt.step()
        assert np.abs(np.asarray(clone.weight._value) - before).max() > 0


class TestTensorArray:
    def test_write_read_stack(self):
        arr = paddle.create_array()
        for i in range(3):
            paddle.array_write(paddle.to_tensor(np.full((2,), i, np.float32)),
                               i, arr)
        assert int(paddle.array_length(arr).item()) == 3
        np.testing.assert_allclose(
            np.asarray(paddle.array_read(arr, 1)._value), 1.0)
        st = arr.stack()
        assert tuple(st.shape) == (3, 2)

    def test_out_of_order_write(self):
        arr = paddle.create_array()
        arr.write(2, paddle.to_tensor(np.ones((1,), np.float32)))
        assert len(arr) == 3
        with pytest.raises(IndexError):
            arr.read(0)
        with pytest.raises(ValueError, match="never written"):
            arr.stack()

    def test_in_to_static_loop(self):
        from paddle_tpu import jit

        @jit.to_static
        def f(x):
            arr = paddle.create_array()
            for i in range(4):
                paddle.array_write(x * float(i), i, arr)
            return arr.stack()

        out = f(paddle.to_tensor(np.ones((2,), np.float32)))
        np.testing.assert_allclose(np.asarray(out._value)[:, 0], [0, 1, 2, 3])


class TestSelectedRows:
    def test_to_dense_and_merge(self):
        vals = paddle.to_tensor(np.array([[1., 2.], [3., 4.], [5., 6.]],
                                         np.float32))
        sr = paddle.SelectedRows(np.array([1, 3, 1]), vals, height=5)
        dense = np.asarray(sr.to_dense()._value)
        np.testing.assert_allclose(dense[1], [6., 8.])  # duplicate summed
        np.testing.assert_allclose(dense[3], [3., 4.])
        np.testing.assert_allclose(dense[0], 0.0)

        merged = sr.merge()
        assert merged.rows.shape[0] == 2
        np.testing.assert_allclose(np.asarray(merged.to_dense()._value), dense)


def _double(x):
    return x * 2


def _add_tensors(a, b):
    return a + b


def _rpc_rank_fn(master_ep):
    import os

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed import rpc

    rank = int(os.environ["PADDLE_TRAINER_ID"])
    rpc.init_rpc(f"worker{rank}", rank=rank, world_size=2,
                 master_endpoint=master_ep)
    peer = f"worker{1 - rank}"
    out = rpc.rpc_sync(peer, _double, args=(10 + rank,))
    t = rpc.rpc_sync(peer, _add_tensors, args=(
        paddle.to_tensor(np.ones((3,), np.float32)),
        paddle.to_tensor(np.full((3,), float(rank), np.float32))))
    infos = [w.name for w in rpc.get_all_worker_infos()]
    rpc.shutdown()
    return out, np.asarray(t._value).tolist(), infos


class TestRpc:
    def test_single_worker_sync_async(self):
        from paddle_tpu.distributed import rpc

        rpc.init_rpc("me", rank=0, world_size=1,
                     master_endpoint="127.0.0.1:0")
        try:
            assert rpc.rpc_sync("me", _double, args=(21,)) == 42
            fut = rpc.rpc_async("me", _double, args=(5,))
            assert fut.result(timeout=30) == 10
            info = rpc.get_worker_info()
            assert info.name == "me" and info.rank == 0
            with pytest.raises(RuntimeError, match="rank exploded"):
                rpc.rpc_sync("me", _boom)
        finally:
            rpc.shutdown()

    def test_two_workers_cross_call(self):
        import socket

        import paddle_tpu.distributed as dist

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        results = dist.spawn(_rpc_rank_fn, args=(f"127.0.0.1:{port}",),
                             nprocs=2, timeout=120)
        for rank, (out, tvals, infos) in enumerate(results):
            assert out == 2 * (10 + rank)       # own args, evaluated remotely
            np.testing.assert_allclose(tvals, 1.0 + rank)
            assert infos == ["worker0", "worker1"]


def _ps_role(master_ep):
    """Two-process PS world: rank 0 = server, rank 1 = worker training a tiny
    embedding regression through pull/push (dense + sparse paths)."""
    import os

    import numpy as np

    from paddle_tpu.distributed import rpc
    from paddle_tpu.distributed.ps import ParameterServer, PSWorker

    rank = int(os.environ["PADDLE_TRAINER_ID"])
    rpc.init_rpc(f"ps{rank}" if rank == 0 else f"trainer{rank}", rank=rank,
                 world_size=2, master_endpoint=master_ep)
    try:
        if rank == 0:
            # server idles; workers drive it through rpc. Barrier on shutdown.
            return "server"
        w = PSWorker("ps0")
        shape = w.create_table("emb", (8, 4), lr=0.5,
                               init=np.ones((8, 4), np.float32))
        assert tuple(shape) == (8, 4)
        # sparse: rows 1 and 1 (duplicate) and 3 get gradients
        ids = np.array([1, 1, 3])
        grads = np.ones((3, 4), np.float32)
        w.push_sparse("emb", ids, grads)
        rows = w.pull_sparse("emb", np.array([1, 3, 0]))
        # row1: 1 - 0.5*2 = 0; row3: 1 - 0.5 = 0.5; row0 untouched
        ok = (abs(rows[0][0]) < 1e-6 and abs(rows[1][0] - 0.5) < 1e-6
              and abs(rows[2][0] - 1.0) < 1e-6)
        # dense path
        w.push_dense("emb", np.full((8, 4), 0.1, np.float32))
        after = w.pull_dense("emb")
        ok = ok and abs(after[2][0] - (1.0 - 0.05)) < 1e-6
        return "ok" if ok else f"mismatch {rows}"
    finally:
        rpc.shutdown()


class TestParameterServer:
    def test_ps_sparse_and_dense_over_processes(self):
        import socket

        import paddle_tpu.distributed as dist

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        results = dist.spawn(_ps_role, args=(f"127.0.0.1:{port}",), nprocs=2,
                             timeout=180)
        assert results[0] == "server"
        assert results[1] == "ok", results[1]


def _dist_dag_role(master_ep):
    """Two-process fleet-executor world with cross-rank dependency edges:
      rank0: load -> [compute0]          compute0 feeds rank1's join
      rank1: compute1(load from rank0) -> join(compute0, compute1)
    """
    import os

    from paddle_tpu.distributed import DistFleetExecutor, TaskNode, rpc

    rank = int(os.environ["PADDLE_TRAINER_ID"])
    rpc.init_rpc(f"fe{rank}", rank=rank, world_size=2,
                 master_endpoint=master_ep)
    try:
        load = TaskNode("load", lambda r, u: 10 + r, rank=0)
        c0 = TaskNode("compute0", lambda r, u: u["load"] * 2, rank=0)
        c1 = TaskNode("compute1", lambda r, u: u["load"] + 1, rank=1)
        join = TaskNode("join", lambda r, u: u["compute0"] + u["compute1"],
                        rank=1)
        c0.add_upstream_task(load)
        c1.add_upstream_task(load)          # cross-rank edge 0 -> 1
        join.add_upstream_task(c0)          # cross-rank edge 0 -> 1
        join.add_upstream_task(c1)
        ex = DistFleetExecutor([load, c0, c1, join], rank=rank,
                               result_timeout=60)
        res = ex.run(num_micro_batches=2)
        if rank == 0:
            assert res["load"] == [10, 11], res
            assert res["compute0"] == [20, 22], res
            return "rank0-ok"
        # round r: join = (10+r)*2 + (10+r) + 1
        assert res["compute1"] == [11, 12], res
        assert res["join"] == [31, 34], res
        return "rank1-ok"
    finally:
        rpc.shutdown()


class TestDistFleetExecutor:
    def test_cross_process_dag(self):
        import socket

        import paddle_tpu.distributed as dist

        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        results = dist.spawn(_dist_dag_role, args=(f"127.0.0.1:{port}",),
                             nprocs=2, timeout=180)
        assert results[0] == "rank0-ok", results[0]
        assert results[1] == "rank1-ok", results[1]


def _has_cryptography() -> bool:
    try:
        import cryptography  # noqa: F401
        return True
    except ImportError:
        return False


@pytest.mark.skipif(not _has_cryptography(),
                    reason="optional 'cryptography' package not installed")
class TestCrypto:
    def test_roundtrip_bytes_and_files(self, tmp_path):
        from paddle_tpu.crypto import Cipher, CipherFactory, CipherUtils

        key = CipherUtils.gen_key(256)
        c = CipherFactory.create_cipher()
        msg = b"model weights \x00\x01" * 100
        blob = c.encrypt(msg, key)
        assert blob != msg and msg not in blob
        assert c.decrypt(blob, key) == msg

        p = tmp_path / "enc.bin"
        c.encrypt_to_file(msg, key, str(p))
        assert c.decrypt_from_file(key, str(p)) == msg

        kf = tmp_path / "k.key"
        k2 = CipherUtils.gen_key_to_file(256, str(kf))
        assert CipherUtils.read_key_from_file(str(kf)) == k2

    def test_tamper_and_wrong_key_detected(self, tmp_path):
        from paddle_tpu.crypto import Cipher, CipherUtils

        c = Cipher()
        key = CipherUtils.gen_key(256)
        blob = bytearray(c.encrypt(b"secret", key))
        blob[-1] ^= 0xFF
        with pytest.raises(Exception):
            c.decrypt(bytes(blob), key)
        with pytest.raises(Exception):
            c.decrypt(c.encrypt(b"secret", key), CipherUtils.gen_key(256))

    def test_encrypted_checkpoint_roundtrip(self, tmp_path):
        from paddle_tpu import crypto, nn

        layer = nn.Linear(3, 2)
        path = tmp_path / "m.pdparams"
        paddle.save(layer.state_dict(), str(path))
        key = crypto.CipherUtils.gen_key(256)
        crypto.encrypt_file(str(path), str(path) + ".enc", key)
        crypto.decrypt_file(str(path) + ".enc", str(tmp_path / "dec"), key)
        sd = paddle.load(str(tmp_path / "dec"))
        np.testing.assert_allclose(np.asarray(sd["weight"]._value if hasattr(sd["weight"], "_value") else sd["weight"]),
                                   np.asarray(layer.weight._value))


class TestFleetExecutor:
    def test_dag_order_and_concurrency(self):
        import time

        from paddle_tpu.distributed import FleetExecutor, TaskNode

        order = []
        lock = __import__("threading").Lock()

        def mk(name, delay=0.0):
            def fn(rnd, ups):
                time.sleep(delay)
                with lock:
                    order.append((rnd, name))
                return f"{name}@{rnd}" , dict(ups)
            return fn

        a = TaskNode("load", mk("load"))
        b = TaskNode("left", mk("left", 0.05))
        c = TaskNode("right", mk("right", 0.05))
        d = TaskNode("join", mk("join"))
        b.add_upstream_task(a)
        c.add_upstream_task(a)
        d.add_upstream_task(b)
        d.add_upstream_task(c)

        t0 = time.perf_counter()
        res = FleetExecutor([a, b, c, d]).run(num_micro_batches=2)
        dt = time.perf_counter() - t0
        assert len(res["join"]) == 2
        # join saw both upstream results
        _, ups = res["join"][0]
        assert set(ups) == {"left", "right"}
        # per round, load precedes branches precedes join
        for rnd in (0, 1):
            names = [n for r, n in order if r == rnd]
            assert names.index("load") < names.index("left")
            assert names.index("join") > names.index("right")
        # branches overlapped (2 rounds x 2 x 0.05s serial would be >=0.2)
        assert dt < 0.19

    def test_cycle_rejected_and_errors_propagate(self):
        from paddle_tpu.distributed import FleetExecutor, TaskNode

        a = TaskNode("a", lambda r, u: 1)
        b = TaskNode("b", lambda r, u: 1)
        a.add_upstream_task(b)
        b.add_upstream_task(a)
        with pytest.raises(ValueError, match="cycle"):
            FleetExecutor([a, b])

        def boom(r, u):
            raise RuntimeError("task failed")

        x = TaskNode("x", boom)
        with pytest.raises(RuntimeError, match="task failed"):
            FleetExecutor([x]).run(1)

    def test_max_run_times(self):
        from paddle_tpu.distributed import FleetExecutor, TaskNode

        t = TaskNode("t", lambda r, u: r, max_run_times=2)
        res = FleetExecutor([t]).run(4)
        assert res["t"] == [0, 1, None, None]

    def test_reverse_declaration_small_pool_no_deadlock(self):
        # Regression (advisor r3): a chain declared downstream-first with a
        # pool smaller than the node count deadlocked the pre-submit
        # scheduler — every slot held a thread waiting on an upstream that
        # could never be scheduled. Completion-driven scheduling must finish.
        from paddle_tpu.distributed import FleetExecutor, TaskNode

        a = TaskNode("a", lambda r, u: 1)
        b = TaskNode("b", lambda r, u: u["a"] + 1)
        c = TaskNode("c", lambda r, u: u["b"] + 1)
        b.add_upstream_task(a)
        c.add_upstream_task(b)
        ex = FleetExecutor([c, b, a], max_workers=2)

        import threading

        out: dict = {}

        def go():
            out["res"] = ex.run(num_micro_batches=3)

        th = threading.Thread(target=go, daemon=True)
        th.start()
        th.join(timeout=20)
        assert not th.is_alive(), "FleetExecutor.run deadlocked"
        assert out["res"]["c"] == [3, 3, 3]

    def test_wide_dag_exceeding_pool(self):
        from paddle_tpu.distributed import FleetExecutor, TaskNode

        sink = TaskNode("sink", lambda r, u: sum(u.values()))
        nodes = []
        for i in range(10):
            n = TaskNode(f"n{i}", lambda r, u, i=i: i)
            sink.add_upstream_task(n)
            nodes.append(n)
        res = FleetExecutor([sink] + nodes, max_workers=3).run(2)
        assert res["sink"] == [45, 45]


class TestEnforceAndNanCheck:
    def test_enforce_taxonomy(self):
        from paddle_tpu.core import enforce as E

        with pytest.raises(E.InvalidArgumentError):
            E.enforce(False, "bad arg")
        with pytest.raises(E.EnforceNotMet):
            E.enforce_eq(1, 2, "mismatch")
        with pytest.raises(E.NotFoundError):
            E.enforce_not_none(None, "missing")
        assert E.enforce_not_none(5) == 5
        with pytest.raises(E.InvalidArgumentError, match="shape mismatch"):
            E.enforce_shape_match((2, 3), (3, 2))
        # typed errors remain catchable as their builtin bases
        with pytest.raises(ValueError):
            E.enforce(False)

    def test_check_nan_inf_covers_compiled_programs(self):
        import jax

        from paddle_tpu.core import flags

        from paddle_tpu import jit as pjit

        flags.set_flags({"check_nan_inf": True})
        try:
            assert jax.config.jax_debug_nans

            @pjit.to_static
            def f(x):
                return (x - x) / (x - x)  # 0/0 -> NaN inside the compiled program

            with pytest.raises(FloatingPointError):
                f(paddle.to_tensor(np.ones((4,), np.float32))).numpy()
        finally:
            flags.set_flags({"check_nan_inf": False})
            assert not jax.config.jax_debug_nans


# --------------------------------------------------------------------------
# one spelling for every setting: what is left a flag is read somewhere, and
# what became a constructor default kept the value its flag had
# --------------------------------------------------------------------------

def _package_sources():
    import glob
    import os

    root = os.path.dirname(os.path.abspath(paddle.__file__))
    return sorted(glob.glob(os.path.join(root, "**", "*.py"), recursive=True))


@functools.lru_cache(maxsize=None)
def _flag_definitions_and_string_reads():
    """({flag: defining file}, {every string constant outside a define_flag
    call}) over the package's sources."""
    import ast

    defined, strings = {}, set()

    def walk(node, path):
        f = getattr(node, "func", None)
        callee = getattr(f, "id", getattr(f, "attr", None))
        if isinstance(node, ast.Call) and callee == "define_flag":
            defined[node.args[0].value] = path
            return                      # a flag's own definition is no read
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            strings.add(node.value)
        for child in ast.iter_child_nodes(node):
            walk(child, path)

    for path in _package_sources():
        with open(path) as f:
            walk(ast.parse(f.read()), path)
    return defined, strings


# the flags that nothing set, by the constructor argument or constant that
# holds each one's value now
_REMOVED_FLAGS = (
    "eager_op_jit", "low_precision_op_list", "use_donated_buffers",
    "benchmark", "elastic",
    "serving_block_size", "serving_slots", "serving_kv_blocks",
    "serving_prefill_chunk", "serving_max_model_len", "serving_prefix_cache",
    "serving_prefill_bucket", "serving_spec_k", "serving_spec_ngram",
    "serving_spec_pause", "fleet_replicas", "fleet_hedge_ttft_ms",
    "fleet_breaker_errors", "fleet_breaker_cooldown_s", "fleet_roles",
    "fleet_drain_migrate", "fleet_scale_min", "fleet_scale_max",
    "fleet_scale_hi", "fleet_scale_lo", "fleet_scale_cooldown_s",
    "fleet_respawn_max", "fleet_respawn_backoff_s", "fleet_warmup_timeout_s",
    "elastic_heartbeat_s", "elastic_lease_ttl_s", "elastic_rebalance_skew",
    "straggler_k", "straggler_m", "serving_flight_requests",
    "fleet_flight_requests", "fleet_detector_window", "autotune_cache_size",
    "weight_only_dequant_cache")


def _default_of(fn, arg):
    import inspect

    return inspect.signature(fn).parameters[arg].default


def _built():
    """Everything the moved defaults live on, built with no arguments."""
    from paddle_tpu.distributed.elastic import ElasticMembership
    from paddle_tpu.distributed.env import InProcStore
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.observability.cluster import ClusterTelemetry
    from paddle_tpu.resilience.elastic import MicroBatchRebalancer
    from paddle_tpu.serving import FleetAutoscaler, FleetRouter, ServingEngine
    from paddle_tpu.serving.fleet_proc import ProcessReplicaSpec

    model = GPTForCausalLM(GPTConfig.tiny())
    engine = ServingEngine(model)
    router = FleetRouter([engine])
    return {
        "engine": engine, "router": router,
        "scaler": FleetAutoscaler(router, spawn=lambda: None),
        "spec": ProcessReplicaSpec(("127.0.0.1", 1)),
        "membership": ElasticMembership(InProcStore(), 0, [0]),
        "rebalancer": MicroBatchRebalancer(),
        "cluster": ClusterTelemetry(InProcStore(), 0, 1),
    }


def _moved_defaults():
    from paddle_tpu.core import autotune
    from paddle_tpu.quantization import weight_only
    from paddle_tpu.serving import FleetRouter, build_fleet
    from paddle_tpu.serving.fleet_observability import FleetObservability
    from paddle_tpu.serving.observability import ServingObservability

    return [
        # (the flag it was, how to read it off what _built() made, value)
        ("serving_slots", lambda b: b["engine"].max_slots, 4),
        ("serving_block_size", lambda b: b["engine"].block_size, 16),
        ("serving_prefill_chunk", lambda b: b["engine"].prefill_chunk, 32),
        # 0 = the model's positions (256), and a pool that holds every slot
        # at that length: 4 * 256 / 16 + the null block
        ("serving_max_model_len", lambda b: b["engine"].max_model_len, 256),
        ("serving_kv_blocks", lambda b: b["engine"].num_blocks, 65),
        ("serving_prefix_cache", lambda b: b["engine"].prefix_cache, True),
        ("serving_prefill_bucket", lambda b: b["engine"].prefill_bucket, 16),
        ("serving_spec_k", lambda b: b["engine"].spec_k, 0),
        ("serving_spec_ngram", lambda b: b["engine"].spec_ngram, 3),
        ("serving_spec_pause", lambda b: b["engine"].spec_pause, 32),
        ("fleet_replicas", lambda b: _default_of(build_fleet, "n_replicas"), 2),
        ("fleet_hedge_ttft_ms", lambda b: b["router"].hedge_ttft_s, 0.0),
        ("fleet_breaker_errors", lambda b: b["router"]._breaker_cfg[0], 3),
        ("fleet_breaker_cooldown_s",
         lambda b: b["router"]._breaker_cfg[1], 2.0),
        ("fleet_roles",
         lambda b: [r.role for r in b["router"].replicas.values()], ["any"]),
        ("fleet_drain_migrate",
         lambda b: _default_of(FleetRouter.drain, "migrate"), False),
        ("fleet_scale_min", lambda b: b["scaler"].min_replicas, 1),
        ("fleet_scale_max", lambda b: b["scaler"].max_replicas, 8),
        ("fleet_scale_hi", lambda b: b["scaler"].hi, 0.85),
        ("fleet_scale_lo", lambda b: b["scaler"].lo, 0.25),
        ("fleet_scale_cooldown_s", lambda b: b["scaler"].cooldown_s, 5.0),
        ("fleet_respawn_max", lambda b: b["spec"].respawn_max, 3),
        ("fleet_respawn_backoff_s",
         lambda b: b["spec"].respawn_backoff_s, 0.5),
        ("fleet_warmup_timeout_s",
         lambda b: b["spec"].warmup_timeout_s, 60.0),
        ("elastic_heartbeat_s", lambda b: b["membership"].heartbeat_s, 0.25),
        ("elastic_lease_ttl_s", lambda b: b["membership"].lease_ttl_s, 1.5),
        ("elastic_rebalance_skew", lambda b: b["rebalancer"].skew, 0.0),
        ("straggler_k",
         lambda b: (b["cluster"].k, b["rebalancer"].k), (2.0, 2.0)),
        ("straggler_m", lambda b: (b["cluster"].m, b["rebalancer"].m), (3, 3)),
        ("serving_flight_requests",
         lambda b: (ServingObservability.FLIGHT_REQUESTS,
                    b["engine"].obs._records.maxlen), (64, 64)),
        ("fleet_flight_requests",
         lambda b: (FleetObservability.FLIGHT_REQUESTS,
                    b["router"].obs._settled.maxlen), (64, 64)),
        ("fleet_detector_window",
         lambda b: (FleetObservability.DETECTOR_WINDOW,
                    b["router"].obs.window), (16, 16)),
        ("autotune_cache_size", lambda b: autotune._CACHE_SIZE, 512),
        # "auto": on wherever there is no int8 GEMM, which the CPU is
        ("weight_only_dequant_cache",
         lambda b: weight_only._dequant_cache_enabled(), True),
    ]


class TestFlagsAndDefaults:
    @pytest.fixture(scope="class")
    def built(self):
        return _built()

    def test_every_flag_is_read(self):
        """A flag that nothing in the package reads is a setting that sets
        nothing: its name has to appear, as a string of its own, somewhere
        outside its definition."""
        defined, strings = _flag_definitions_and_string_reads()
        assert len(defined) >= 30          # the walk found the registry
        dead = sorted(n for n in defined if n not in strings)
        assert not dead, f"flags defined and never read: {dead}"

    def test_no_removed_flag_is_defined(self):
        defined, _ = _flag_definitions_and_string_reads()
        assert not set(_REMOVED_FLAGS) & set(defined)
        assert len(defined) <= 35
        moved = {name for name, _, _ in _moved_defaults()}
        # every removed setting that had a reader is held by a case below
        assert moved == set(_REMOVED_FLAGS[5:])

    @pytest.mark.parametrize("flag", _REMOVED_FLAGS[5:])
    def test_default_kept_the_flags_value(self, built, flag):
        (read, want), = [(r, w) for n, r, w in _moved_defaults() if n == flag]
        assert read(built) == want
