"""Round-5 parity batch 2: linalg namespace, distributed long tail
(object collectives, gloo compat, entries, QueueDataset), and the static
module extras (tape gradients, py_func, EMA, serialization, scopes).

Reference __all__ lists: python/paddle/{linalg.py,distributed/__init__.py,
static/__init__.py,optimizer/__init__.py}."""
import ast
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
import paddle_tpu.static as static


def _ref_all(path):
    p = pathlib.Path(path)
    if not p.exists():
        return None
    for node in ast.walk(ast.parse(p.read_text())):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == "__all__":
                    return [ast.literal_eval(e) for e in node.value.elts]
    return None


@pytest.mark.parametrize("mod,path", [
    (paddle.linalg, "/root/reference/python/paddle/linalg.py"),
    (dist, "/root/reference/python/paddle/distributed/__init__.py"),
    (static, "/root/reference/python/paddle/static/__init__.py"),
    (paddle.optimizer, "/root/reference/python/paddle/optimizer/__init__.py"),
])
def test_namespace_parity(mod, path):
    ref = _ref_all(path)
    if ref is None:
        pytest.skip("reference absent")
    missing = [n for n in ref if not hasattr(mod, n)]
    assert missing == [], f"{mod.__name__} missing: {missing}"


def test_linalg_numerics():
    rng = np.random.RandomState(0)
    a = rng.randn(4, 4).astype(np.float32)
    spd = a @ a.T + 4 * np.eye(4, dtype=np.float32)
    t = paddle.to_tensor(spd)
    assert np.allclose(paddle.linalg.inv(t).numpy() @ spd, np.eye(4),
                       atol=1e-4)
    u, s, v = paddle.linalg.pca_lowrank(paddle.to_tensor(
        rng.randn(10, 6).astype(np.float32)), q=3)
    assert u.shape == [10, 3] and s.shape == [3] and v.shape == [6, 3]
    # V columns are orthonormal
    assert np.allclose(v.numpy().T @ v.numpy(), np.eye(3), atol=1e-4)


def test_object_collectives_single_process():
    from paddle_tpu.distributed import objects as O

    got = []
    O.all_gather_object(got, {"x": 1})
    assert got == [{"x": 1}]
    lst = [1, 2]
    O.broadcast_object_list(lst)
    assert lst == [1, 2]
    out = []
    O.scatter_object_list(out, ["only"])
    assert out == ["only"]
    assert O.get_backend() == "XLA" and O.is_available()
    O.wait(paddle.to_tensor(np.ones(2, np.float32)))


def test_object_collectives_cross_process():
    """Two real processes exchange objects over the native TCPStore."""
    code = r"""
import os, sys
sys.path.insert(0, os.environ["REPO_ROOT"])
from paddle_tpu.distributed import objects as O
rank = int(os.environ["PADDLE_TRAINER_ID"])
O.gloo_init_parallel_env(rank, 2, os.environ["STORE_EP"])
got = []
O.all_gather_object(got, {"rank": rank, "val": rank * 10})
assert got == [{"rank": 0, "val": 0}, {"rank": 1, "val": 10}], got
lst = [None]
if rank == 0:
    lst = [{"from0": True}]
O.broadcast_object_list(lst, src=0)
assert lst == [{"from0": True}], lst
out = []
O.scatter_object_list(out, ["a", "b"] if rank == 0 else None, src=0)
assert out == [["a", "b"][rank]], out
O.gloo_barrier()
print("RANK_OK", rank)
"""
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    procs = []
    for r in range(2):
        env = dict(os.environ, PADDLE_TRAINER_ID=str(r),
                   PADDLE_TRAINERS_NUM="2",
                   STORE_EP=f"127.0.0.1:{port}", JAX_PLATFORMS="cpu",
                   REPO_ROOT=os.path.dirname(os.path.dirname(
                       os.path.abspath(__file__))))
        procs.append(subprocess.Popen([sys.executable, "-c", code], env=env,
                                      stdout=subprocess.PIPE,
                                      stderr=subprocess.STDOUT, text=True))
    outs = [p.communicate(timeout=120)[0] for p in procs]
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out[-2000:]}"
        assert f"RANK_OK {r}" in out


def test_ps_entry_admission():
    from paddle_tpu.distributed.ps import (CountFilterEntry, ParameterServer,
                                           ProbabilityEntry)

    ParameterServer.reset()
    ParameterServer.create_table("emb", (10, 4), lr=1.0, optimizer="sgd",
                                 entry=CountFilterEntry(3))
    before = ParameterServer.pull_sparse("emb", [2])[0].copy()
    g = np.ones((1, 4), np.float32)
    ParameterServer.push_sparse("emb", [2], g)   # count 1: filtered
    ParameterServer.push_sparse("emb", [2], g)   # count 2: filtered
    assert np.allclose(ParameterServer.pull_sparse("emb", [2])[0], before)
    ParameterServer.push_sparse("emb", [2], g)   # count 3: admitted
    after = ParameterServer.pull_sparse("emb", [2])[0]
    assert not np.allclose(after, before)
    # probability 0 never admits; probability 1 always admits
    ParameterServer.create_table("p0", (4, 2), lr=1.0,
                                 entry=ProbabilityEntry(0.0))
    b = ParameterServer.pull_sparse("p0", [1])[0].copy()
    ParameterServer.push_sparse("p0", [1], np.ones((1, 2), np.float32))
    assert np.allclose(ParameterServer.pull_sparse("p0", [1])[0], b)
    ParameterServer.reset()


def test_queue_dataset_streams(tmp_path):
    files = []
    for i in range(2):
        f = tmp_path / f"part{i}.txt"
        # one dense slot (1 value) + one sparse slot (i+1 values per line)
        f.write_text("\n".join(
            f"1 {j + i * 10} {i + 1} " + " ".join(
                str(j) for _ in range(i + 1))
            for j in range(4)))
        files.append(str(f))
    ds = dist.QueueDataset()
    ds.init(batch_size=2, slots=[("d", "dense"), ("s", "sparse")])
    ds.set_filelist(files)
    batches = list(ds)
    assert len(batches) == 4  # 8 records / batch 2, streamed per file
    with pytest.raises(RuntimeError):
        ds.global_shuffle()
    with pytest.raises(RuntimeError):
        ds.load_into_memory()


def test_static_gradients_and_append_backward():
    paddle.enable_static()
    try:
        prog = static.Program()
        with static.program_guard(prog):
            x = static.data("x", [4, 3])
            w = paddle.create_parameter([3, 1])
            loss = paddle.mean(paddle.matmul(x, w))
            (gx,) = static.gradients([loss], [x])
            pgs = static.append_backward(loss)
        exe = static.Executor()
        out = exe.run(prog, feed={"x": np.ones((4, 3), np.float32)},
                      fetch_list=[loss, gx, pgs[0][1]])
        # dmean/dx[i,j] = w[j]/4 ; dmean/dw[j] = sum_i x[i,j]/4 = 1
        assert np.allclose(out[1], np.tile(w.numpy().T / 4, (4, 1)),
                           atol=1e-5)
        assert np.allclose(out[2], np.ones((3, 1)), atol=1e-5)
    finally:
        paddle.disable_static()


def test_static_py_func_and_print():
    paddle.enable_static()
    try:
        prog = static.Program()
        with static.program_guard(prog):
            x = static.data("x", [3])
            out = paddle.zeros([3])  # shape/dtype template variable
            static.py_func(lambda v: v * 2 + 1, x, out)
            p = static.Print(out, message="pyfunc out")
        exe = static.Executor()
        res = exe.run(prog, feed={"x": np.array([1., 2., 3.], np.float32)},
                      fetch_list=[p])
        assert np.allclose(res[0], [3., 5., 7.])
    finally:
        paddle.disable_static()


def test_program_serialization_roundtrip():
    paddle.enable_static()
    try:
        prog = static.Program()
        with static.program_guard(prog):
            x = static.data("x", [2, 3])
            w = paddle.create_parameter([3, 2])
            y = paddle.matmul(x, w)
            z = paddle.tanh(y)
        data = static.serialize_program(program=prog)
        params = static.serialize_persistables(program=prog)
        prog2 = static.deserialize_program(data)
        static.deserialize_persistables(prog2, params)
        exe = static.Executor()
        feed = {"x": np.random.RandomState(0).randn(2, 3).astype(np.float32)}
        a = exe.run(prog, feed=feed, fetch_list=[z])[0]
        z2 = prog2._ops[-1].out_tensors[0]
        b = exe.run(prog2, feed=feed, fetch_list=[z2])[0]
        assert np.allclose(a, b, atol=1e-6)
    finally:
        paddle.disable_static()


def test_scope_and_places_and_strategies():
    sc = static.Scope()
    with static.scope_guard(sc):
        static.global_scope().var("k").set(np.ones(3))
        assert np.allclose(static.global_scope().find_var("k").get_tensor(),
                           1)
    assert static.global_scope() is not sc
    assert len(static.cpu_places(2)) == 2
    bs = static.BuildStrategy()
    cp = static.CompiledProgram(static.Program(), build_strategy=bs)
    assert cp.with_data_parallel() is cp
    with pytest.raises(RuntimeError):
        static.IpuStrategy()


def test_exponential_moving_average():
    paddle.enable_static()
    try:
        prog = static.Program()
        with static.program_guard(prog):
            x = static.data("x", [2, 2])
            w = paddle.create_parameter([2, 2])
            paddle.matmul(x, w)
        ema = static.ExponentialMovingAverage(decay=0.5)
        with static.program_guard(prog):
            ema.update()
        w0 = w.numpy().copy()
        w._value = w._value + 10.0
        with static.program_guard(prog):
            ema.update()
            with ema.apply():
                applied = w.numpy().copy()
            restored = w.numpy()
        # zero-seeded shadow, two updates at decay 0.5:
        # s = 0.5*(0.5*w0) + 0.5*(w0+10) = 0.75*w0 + 5; corr = 1-0.25
        assert np.allclose(applied, (0.75 * w0 + 5) / 0.75, atol=1e-4)
        assert np.allclose(restored, w0 + 10)
    finally:
        paddle.disable_static()


def test_static_accuracy_auc():
    paddle.enable_static()
    try:
        logits = paddle.to_tensor(
            np.array([[0.9, 0.1], [0.2, 0.8], [0.6, 0.4]], np.float32))
        labels = paddle.to_tensor(np.array([0, 1, 1], np.int64))
        acc = static.accuracy(logits, labels)
        assert abs(float(np.asarray(acc._value)) - 2 / 3) < 1e-5
        a, *_ = static.auc(logits, labels)
        assert 0.0 <= float(np.asarray(a._value)) <= 1.0
    finally:
        paddle.disable_static()


def test_batch1_module_parity():
    """amp/jit/sparse/fft/incubate/utils/geometric/quantization/device/
    nn.initializer/nn.utils/optimizer.lr/regularizer/profiler/callbacks/
    hub/sysconfig all resolve their reference __all__ names."""
    R = "/root/reference/python/paddle/"
    mods = ["amp", "jit", "sparse", "sparse/nn", "fft", "incubate", "utils",
            "geometric", "quantization", "device", "nn/initializer",
            "nn/utils", "optimizer/lr", "regularizer", "profiler",
            "callbacks", "hub", "sysconfig"]
    problems = {}
    for m in mods:
        ref = None
        for cand in (R + m + "/__init__.py", R + m + ".py"):
            ref = _ref_all(cand)
            if ref is not None:
                break
        if ref is None:
            continue
        mod = paddle
        for part in m.replace("/", ".").split("."):
            mod = getattr(mod, part, None)
            if mod is None:
                break
        if mod is None:
            problems[m] = "MODULE MISSING"
            continue
        missing = [n for n in ref if not hasattr(mod, n)]
        if missing:
            problems[m] = missing
    assert problems == {}, problems


def test_l1_l2_decay_behavior():
    paddle.seed(0)
    m = paddle.nn.Linear(4, 2)
    opt = paddle.optimizer.SGD(0.1, parameters=m.parameters(),
                               weight_decay=paddle.regularizer.L1Decay(0.5))
    w0 = m.weight.numpy().copy()
    x = paddle.to_tensor(np.zeros((1, 4), np.float32))
    loss = m(x).sum()
    loss.backward()
    opt.step()
    # zero input -> zero data grad for weight; only L1 decay moves it
    assert np.allclose(m.weight.numpy(), w0 - 0.1 * 0.5 * np.sign(w0),
                       atol=1e-6)


def test_hermitian_fft_roundtrips():
    x = paddle.to_tensor(np.random.RandomState(0).randn(4, 6)
                         .astype(np.float32))
    assert np.allclose(paddle.fft.hfft2(paddle.fft.ihfft2(x)).numpy(),
                       x.numpy(), atol=1e-4)
    assert np.allclose(paddle.fft.hfftn(paddle.fft.ihfftn(x)).numpy(),
                       x.numpy(), atol=1e-4)


def test_weight_and_spectral_norm_utils():
    from paddle_tpu.nn import utils as U

    m = paddle.nn.Linear(4, 3)
    x = paddle.to_tensor(np.random.RandomState(0).randn(2, 4)
                         .astype(np.float32))
    U.weight_norm(m, "weight", dim=0)
    y1 = m(x)
    U.remove_weight_norm(m, "weight")
    assert np.allclose(y1.numpy(), m(x).numpy(), atol=1e-5)
    m2 = paddle.nn.Linear(4, 3)
    U.spectral_norm(m2, "weight", n_power_iterations=8)
    m2(x)
    assert abs(np.linalg.norm(m2.__dict__["weight"].numpy(), 2) - 1) < 0.05
    total = U.clip_grad_norm_([p for p in m.parameters()], 1e-9)
    assert float(total.numpy()) >= 0.0


def test_enable_to_static_switch_and_ignore_module():
    from paddle_tpu import jit

    calls = []

    @jit.to_static
    def f(x):
        calls.append(1)  # side effect visible only in dygraph passthrough
        return x * 2

    jit.enable_to_static(False)
    try:
        out = f(paddle.to_tensor(np.array([2.0], np.float32)))
        assert np.allclose(out.numpy(), [4.0]) and calls
    finally:
        jit.enable_to_static(True)


def test_jit_load_returns_translated_layer(tmp_path):
    from paddle_tpu import jit

    class M(paddle.nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = paddle.nn.Linear(4, 2)

        def forward(self, x):
            return self.fc(x)

    m = M()
    path = str(tmp_path / "m")
    jit.save(m, path, input_spec=[jit.InputSpec([1, 4], "float32", "x")])
    loaded = jit.load(path)
    assert isinstance(loaded, jit.TranslatedLayer)
    x = paddle.to_tensor(np.ones((1, 4), np.float32))
    assert np.allclose(loaded(x).numpy(), m(x).numpy(), atol=1e-5)


def test_sparse_reshape_slice_isnan():
    import paddle_tpu.sparse as S

    d = paddle.to_tensor(np.array([[0., 1, 0], [2, 0, 3]], np.float32))
    c = S.to_sparse_coo(d, 2)
    assert np.allclose(S.reshape(c, [3, 2]).to_dense().numpy(),
                       d.numpy().reshape(3, 2))
    assert np.allclose(S.slice(c, [1], [1], [3]).to_dense().numpy(),
                       d.numpy()[:, 1:3])
    assert S.isnan(c).nnz() == 2 or S.isnan(c).nnz() == 3  # pattern nnz


def test_hub_local_repo(tmp_path):
    (tmp_path / "hubconf.py").write_text(
        "def toy(scale=1):\n"
        "    '''a toy entrypoint'''\n"
        "    return {'scale': scale}\n")
    assert "toy" in paddle.hub.list(str(tmp_path))
    assert "toy entrypoint" in paddle.hub.help(str(tmp_path), "toy")
    assert paddle.hub.load(str(tmp_path), "toy", scale=3) == {"scale": 3}


def test_executor_fetch_list_not_cache_aliased():
    """Two runs with different fetch_lists must not share a compiled
    program (regression: the cache key omitted the fetch set)."""
    paddle.enable_static()
    try:
        prog = static.Program()
        with static.program_guard(prog):
            x = static.data("x", [2])
            a = paddle.scale(x, 2.0)
            b = paddle.scale(x, 3.0)
        exe = static.Executor()
        feed = {"x": np.ones(2, np.float32)}
        r1 = exe.run(prog, feed=feed, fetch_list=[a])
        r2 = exe.run(prog, feed=feed, fetch_list=[b])
        r3 = exe.run(prog, feed=feed, fetch_list=[b, a])
        assert np.allclose(r1[0], 2.0) and np.allclose(r2[0], 3.0)
        assert np.allclose(r3[0], 3.0) and np.allclose(r3[1], 2.0)
    finally:
        paddle.disable_static()


def test_executor_training_with_donation_stays_stable():
    """Donated param/opt-state buffers: multi-step static training keeps
    decreasing loss and param dtype (bf16 O2) across retraces."""
    from paddle_tpu import amp

    paddle.seed(0)
    m = paddle.nn.Linear(8, 1)
    m, opt = amp.decorate(
        m, paddle.optimizer.Momentum(0.05, parameters=m.parameters()),
        level="O2", dtype="bfloat16")
    paddle.enable_static()
    try:
        prog = static.Program()
        with static.program_guard(prog):
            x = static.data("x", [16, 8])
            y = static.data("y", [16, 1])
            pred = m(paddle.cast(x, "bfloat16"))
            loss = paddle.mean(paddle.square(
                paddle.subtract(paddle.cast(pred, "float32"), y)))
            opt.minimize(loss)
        exe = static.Executor()
        rng = np.random.RandomState(0)
        feed = {"x": rng.randn(16, 8).astype(np.float32),
                "y": rng.randn(16, 1).astype(np.float32)}
        losses = [float(exe.run(prog, feed=feed, fetch_list=[loss])[0])
                  for _ in range(8)]
        assert losses[-1] < losses[0]
        assert str(m.weight._value.dtype) == "bfloat16"
    finally:
        paddle.disable_static()
