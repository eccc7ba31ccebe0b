"""Fine-grained compute/collective overlap (distributed/overlap.py):
decomposed ring reduce parity, readiness analysis, the deterministic
schedule verifier, TrainStep integration behind FLAGS_dp_overlap, and the
attributed reduce-phase telemetry.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu import analysis, nn, optimizer
from paddle_tpu.core import flags
from paddle_tpu.distributed import overlap
from jax import shard_map
from paddle_tpu.jit.trainer import TrainStep


@pytest.fixture
def mesh8():
    return Mesh(np.array(jax.devices()), ("dp",))


@pytest.fixture(autouse=True)
def _restore_flags():
    keep = {k: flags.get_flag(k) for k in (
        "dp_overlap", "dp_overlap_min_kb", "grad_bucket_mb",
        "jit_fast_dispatch", "metrics", "metrics_dir")}
    yield
    flags.set_flags(keep)


def _mesh(world):
    return Mesh(np.array(jax.devices()[:world]), ("dp",))


def _smap(fn, mesh, n_in, n_out, batch_in=0):
    """shard_map helper: first `batch_in` args split over dp, rest
    replicated; outputs replicated."""
    in_specs = tuple(P("dp") if i < batch_in else P() for i in range(n_in))
    return jax.jit(shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=(P(),) * n_out if n_out > 1 else P(),
                             axis_names=frozenset({"dp"}), check_vma=False))


# ------------------------------------------------------------- ring parity
class TestRingParity:
    @pytest.mark.parametrize("world", [2, 4, 8])
    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                           (jnp.bfloat16, 5e-2)])
    @pytest.mark.parametrize("size", [64, 1000, 10007])  # 10007: uneven pad
    def test_ring_matches_pmean(self, world, dtype, tol, size):
        mesh = _mesh(world)
        x = np.random.RandomState(size % 97).rand(world, size)
        x = jnp.asarray(x, dtype)

        def ring(v):
            return overlap.ring_all_reduce(v.ravel(), "dp", world=world)

        def ref(v):
            return jax.lax.pmean(v.ravel(), "dp")

        f_ring = jax.jit(shard_map(ring, mesh=mesh, in_specs=(P("dp"),),
                                   out_specs=P("dp"),
                                   axis_names=frozenset({"dp"}),
                                   check_vma=False))
        f_ref = jax.jit(shard_map(ref, mesh=mesh, in_specs=(P("dp"),),
                                  out_specs=P("dp"),
                                  axis_names=frozenset({"dp"}),
                                  check_vma=False))
        a = np.asarray(f_ring(x), np.float32)
        b = np.asarray(f_ref(x), np.float32)
        np.testing.assert_allclose(a, b, atol=tol, rtol=tol)

    def test_ring_psum_mode(self, mesh8):
        x = np.random.RandomState(3).rand(8, 257).astype(np.float32)
        f = _smap(lambda v: overlap.ring_all_reduce(
            v.ravel(), "dp", mean=False), mesh8, 1, 1, batch_in=1)
        g = _smap(lambda v: jax.lax.psum(v.ravel(), "dp"), mesh8, 1, 1,
                  batch_in=1)
        np.testing.assert_allclose(np.asarray(f(x)), np.asarray(g(x)),
                                   rtol=1e-5, atol=1e-4)

    def test_reduce_flush_mixed_schedules(self, mesh8):
        """Cost model live: big tensors ring, small ones psum — output
        order and values match plain pmean either way."""
        flags.set_flags({"dp_overlap_min_kb": 8})
        shapes = [(100, 100), (7,), (63, 129), (500,)]
        gs = [np.random.RandomState(i).rand(*s).astype(np.float32) * 4
              for i, s in enumerate(shapes)]

        def perturb(g):  # give each device distinct values to reduce
            s = 1.0 + jax.lax.axis_index("dp").astype(jnp.float32)
            return [x * s for x in g]

        fine = _smap(lambda *g: tuple(overlap.reduce_flush(
            perturb(g), "dp", bucket_bytes=1 << 15)), mesh8, 4, 4)
        ref = _smap(lambda *g: tuple(jax.lax.pmean(x, "dp")
                                     for x in perturb(g)), mesh8, 4, 4)
        for a, b in zip(fine(*gs), ref(*gs)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-5)


# -------------------------------------------------------------- cost model
class TestCostModel:
    def test_world_two_falls_back(self):
        assert overlap.choose_schedule(1 << 24, 2, 100) == "psum"
        assert overlap.choose_schedule(1 << 24, 1, 100) == "psum"

    def test_small_bucket_falls_back(self):
        assert overlap.choose_schedule(1 << 10, 8, 100,
                                       min_bytes=1 << 17) == "psum"
        assert overlap.choose_schedule(1 << 20, 8, 100,
                                       min_bytes=1 << 17) == "ring"

    def test_tail_bucket_needs_4x_floor(self):
        # ready too close to the jaxpr tail (< 2*(world-1) eqns left):
        # nothing to overlap with, so the byte floor quadruples
        floor = 1 << 17
        nbytes = 2 << 17  # clears 1x, not 4x
        assert overlap.choose_schedule(nbytes, 8, 100,
                                       min_bytes=floor) == "ring"
        assert overlap.choose_schedule(nbytes, 8, 3,
                                       min_bytes=floor) == "psum"
        assert overlap.choose_schedule(8 << 17, 8, 3,
                                       min_bytes=floor) == "ring"

    def test_min_ring_bytes_follows_flag(self):
        flags.set_flags({"dp_overlap_min_kb": 7})
        assert overlap.min_ring_bytes() == 7 << 10


# ---------------------------------------------------- readiness (analysis/)
class TestReadiness:
    def test_output_ready_indices(self):
        def fn(x, y):
            a = x + 1.0     # eqn 0
            b = a * y       # eqn 1
            c = jnp.sum(b)  # eqn 2
            return c, a, x

        closed = jax.make_jaxpr(fn)(np.ones(4, np.float32),
                                    np.ones(4, np.float32))
        ready = analysis.output_ready_indices(closed)
        # c needs the last eqn, a only the first, x is a passthrough input
        assert ready[0] == len(closed.jaxpr.eqns) - 1
        assert ready[1] == 0
        assert ready[2] == -1

    def test_bucket_ready_is_max_over_members(self):
        ready = [0, 5, 2, -1]
        assert analysis.bucket_ready_indices(ready, [[0, 1], [2], [3]]) == \
            [5, 2, -1]

    def test_verifier_raise_on_tail_clustered(self, mesh8):
        def step(x, w):
            g = jax.grad(lambda w_: jnp.sum(jnp.tanh(x @ w_) ** 2))(w)
            return jax.lax.pmean(g, "dp")  # single flush at the tail

        closed = jax.make_jaxpr(shard_map(
            step, mesh=mesh8, in_specs=(P("dp"), P()), out_specs=P(),
            axis_names=frozenset({"dp"}), check_vma=False))(
                np.ones((8, 16), np.float32), np.ones((16, 16), np.float32))
        rep = analysis.schedule_report(closed)
        assert rep["tail_clustered"] and rep["interleaved_collectives"] == 0
        with pytest.raises(AssertionError, match="not interleaved"):
            analysis.verify_overlap_schedule(closed, raise_on_fail=True)


# ------------------------------------------------------ TrainStep integration
def _make_model(seed=0):
    paddle.seed(seed)
    return nn.Sequential(nn.Linear(48, 96), nn.GELU(), nn.Linear(96, 48))


def _loss_fn(model):
    def f(x, y):
        return ((model(x) - y) ** 2).mean()
    return f


def _mk_step(mesh, **kw):
    model = _make_model(0)
    opt = optimizer.Momentum(learning_rate=0.05, momentum=0.9,
                             parameters=model.parameters())
    return TrainStep(model, _loss_fn(model), opt, dp_axis="dp", mesh=mesh,
                     **kw)


_X = np.random.RandomState(0).rand(16, 48).astype(np.float32)
_Y = np.random.RandomState(1).rand(16, 48).astype(np.float32)


def _run(step, n=3):
    losses = [float(step(paddle.to_tensor(_X), paddle.to_tensor(_Y)))
              for _ in range(n)]
    return losses, [np.asarray(p._value) for p in step.params]


class TestTrainStepFine:
    def test_fine_matches_single_and_bucketed(self, mesh8):
        flags.set_flags({"dp_overlap_min_kb": 1})
        l_single, p_single = _run(_mk_step(mesh8, grad_bucket_mb=-1))
        l_buck, p_buck = _run(_mk_step(mesh8, grad_bucket_mb=0,
                                       dp_overlap="bucketed"))
        l_fine, p_fine = _run(_mk_step(mesh8, grad_bucket_mb=0,
                                       dp_overlap="fine"))
        np.testing.assert_allclose(l_buck, l_single, rtol=1e-6)
        np.testing.assert_allclose(l_fine, l_single, rtol=1e-5)
        for a, b in zip(p_buck, p_single):
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
        for a, b in zip(p_fine, p_single):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
        sched = overlap.last_schedule()
        assert sched and sched["ring_buckets"] > 0
        assert sched["inline_steps"] > 0  # steps actually interleaved

    def test_fine_schedule_verifier_gate(self, mesh8):
        """Deterministic overlap gate: the fine step's jaxpr interleaves
        collective chunks between backward segments; bucketed clusters
        them at the tail."""
        flags.set_flags({"dp_overlap_min_kb": 1})
        fine = _mk_step(mesh8, grad_bucket_mb=0, dp_overlap="fine")
        buck = _mk_step(mesh8, grad_bucket_mb=0, dp_overlap="bucketed")

        def trace(step):
            return jax.make_jaxpr(step._base_callable)(
                [p._value for p in step.params],
                [b._value for b in step.buffers],
                step.opt_state, jnp.float32(0.05), jnp.int32(0), (_X, _Y))

        rep_fine = analysis.verify_overlap_schedule(trace(fine),
                                                    raise_on_fail=True)
        assert rep_fine["ok"] and not rep_fine["tail_clustered"]
        rep_buck = analysis.schedule_report(trace(buck))
        assert rep_buck["tail_clustered"]

    def test_cost_model_fallback_all_psum(self, mesh8):
        """A huge ring floor turns every bucket into the pmean fallback —
        still exact parity, and the schedule says so."""
        flags.set_flags({"dp_overlap_min_kb": 1 << 20})
        l_fine, p_fine = _run(_mk_step(mesh8, grad_bucket_mb=0,
                                       dp_overlap="fine"))
        sched = overlap.last_schedule()
        assert sched["ring_buckets"] == 0
        assert sched["psum_buckets"] == sched["n_buckets"]
        l_single, p_single = _run(_mk_step(mesh8, grad_bucket_mb=-1))
        np.testing.assert_allclose(l_fine, l_single, rtol=1e-6)
        for a, b in zip(p_fine, p_single):
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)

    def test_flag_flip_retraces(self, mesh8):
        """FLAGS_dp_overlap read at trace time + cfg tracked per call: a
        flip between steps rebuilds the program instead of dispatching the
        stale schedule."""
        flags.set_flags({"dp_overlap": "bucketed", "dp_overlap_min_kb": 1})
        step = _mk_step(mesh8, grad_bucket_mb=0)  # no explicit dp_overlap
        assert step._overlap_mode() == "bucketed"
        float(step(paddle.to_tensor(_X), paddle.to_tensor(_Y)))
        flags.set_flags({"dp_overlap": "fine"})
        assert step._overlap_mode() == "fine"
        overlap._LAST_SCHEDULE = None  # a fine retrace must repopulate it
        float(step(paddle.to_tensor(_X), paddle.to_tensor(_Y)))
        sched = overlap.last_schedule()
        assert sched is not None and sched["mode"] == "fine"

    def test_bad_mode_rejected(self, mesh8):
        with pytest.raises(ValueError, match="dp_overlap"):
            _mk_step(mesh8, dp_overlap="nope")
        flags.set_flags({"dp_overlap": "sideways"})
        step = _mk_step(mesh8)
        with pytest.raises(ValueError, match="sideways"):
            step._overlap_mode()

    def test_fleet_overlap_knob(self, mesh8):
        from paddle_tpu.distributed import fleet as fleet_mod

        strategy = fleet_mod.DistributedStrategy()
        strategy.dp_comm_configs["bucketed_allreduce"] = True
        strategy.dp_comm_configs["overlap"] = "fine"
        model = _make_model(0)
        opt = optimizer.Momentum(learning_rate=0.05, momentum=0.9,
                                 parameters=model.parameters())
        step = fleet_mod.dp_train_step(model, _loss_fn(model), opt,
                                       strategy=strategy, mesh=mesh8)
        assert step._dp_overlap == "fine"
        assert step._overlap_mode() == "fine"


# --------------------------------------------------- telemetry attribution
class TestReduceTelemetry:
    def test_reduce_phase_nonzero_and_phases_sum(self, mesh8, tmp_path):
        from paddle_tpu.observability import telemetry as tele

        flags.set_flags({"metrics": "on", "metrics_dir": str(tmp_path),
                         "dp_overlap_min_kb": 1})
        tele.reset()
        try:
            step = _mk_step(mesh8, grad_bucket_mb=0, dp_overlap="fine",
                            telemetry=True)
            x, y = paddle.to_tensor(_X), paddle.to_tensor(_Y)
            float(step(x, y))  # compile + first probe
            float(step(x, y))  # warm
            t0 = time.perf_counter()
            float(step(x, y))
            wall = time.perf_counter() - t0
            rec = tele.get_telemetry().last_record()
            phases = rec["phases"]
            assert phases["reduce"] > 0.0, "reduce_ms still 0.0 on dp>1"
            assert phases["compute"] > 0.0
            # attribution is a carve-out, not an add-on: phases sum to the
            # step time the host measured (10% acceptance bound, plus a
            # small absolute allowance for host-side record assembly)
            total = sum(phases.values())
            assert abs(total - wall) <= max(0.1 * wall, 0.02), \
                f"phases {phases} sum {total:.4f}s vs wall {wall:.4f}s"
            assert rec["reduce_overlapped"] is True
        finally:
            tele.reset()

    def test_no_probe_without_dp(self):
        model = _make_model(0)
        opt = optimizer.Momentum(learning_rate=0.05, momentum=0.9,
                                 parameters=model.parameters())
        step = TrainStep(model, _loss_fn(model), opt)
        assert step._probe_reduce_s() is None
