"""paddle.distributed.spawn (reference:
python/paddle/distributed/spawn.py:428).

Launches `nprocs` worker processes running func(*args), with the reference's
rank environment (PADDLE_TRAINER_ID / PADDLE_TRAINERS_NUM) set per child.

TPU-native scope: on TPU pods, one process drives many chips through
jax.distributed + the launch CLI (distributed/launch), so spawn is the
single-host developer tool the reference also uses for CPU/GPU tests.

Process model: plain subprocesses with a pickle handoff — NOT
multiprocessing's fork (forking a jax-initialized parent can deadlock in its
thread pools) and NOT multiprocessing's spawn (its main-module fixup
re-executes the parent's __main__, which re-runs the whole test session when
the parent is pytest). `func` must be module-level (pickled by reference).

One process owns a chip: a child started without `backend=` uses whatever
platform jax finds (it inherits JAX_PLATFORMS from the parent's environment),
so on a TPU host it claims the chip — and fails or hangs if the parent or a
sibling already holds it. Pass backend="cpu" for CPU workers.
"""
from __future__ import annotations

import os
import pickle
import subprocess
import sys
import tempfile


class ProcessContext:
    """Reference spawn return object: .processes + .join()."""

    def __init__(self, procs, out_paths, tmpdir):
        self.processes = procs
        self._out_paths = out_paths
        self._tmpdir = tmpdir

    def join(self, timeout=None):
        import time as _time

        results = [None] * len(self.processes)
        errors = []
        deadline = None if timeout is None else _time.monotonic() + timeout
        for i, p in enumerate(self.processes):
            try:
                # one shared deadline across ALL ranks, not timeout-per-rank
                left = None if deadline is None else max(
                    deadline - _time.monotonic(), 0.01)
                p.wait(left)
            except subprocess.TimeoutExpired:
                p.kill()
                errors.append((i, "timeout"))
                continue
            try:
                with open(self._out_paths[i], "rb") as f:
                    kind, payload = pickle.load(f)
                if kind == "ok":
                    results[i] = payload
                else:
                    errors.append((i, payload))
            except FileNotFoundError:
                errors.append((i, f"no result (exitcode {p.returncode})"))
        self._tmpdir.cleanup()
        if errors:
            rank, msg = errors[0]
            raise RuntimeError(f"spawn worker {rank} failed:\n{msg}")
        return results


def _subprocess_main():  # child entry (see spawn below)
    in_path = os.environ["PADDLE_SPAWN_IN"]
    out_path = os.environ["PADDLE_SPAWN_OUT"]
    try:
        with open(in_path, "rb") as f:
            func, args = pickle.load(f)
        out = func(*args)
        payload = ("ok", out)
    except Exception:  # noqa: BLE001 — must cross the process
        import traceback

        payload = ("err", traceback.format_exc())
    with open(out_path + ".tmp", "wb") as f:
        pickle.dump(payload, f)
    os.replace(out_path + ".tmp", out_path)
    if payload[0] == "err":
        sys.exit(1)


def spawn(func, args=(), nprocs=-1, join=True, daemon=False, backend=None,
          timeout=None, **options):
    """Run func in `nprocs` processes; returns ProcessContext (join=False)
    or the list of per-rank return values (join=True). `backend` sets the
    children's JAX_PLATFORMS; None leaves the platform to jax (see the
    module docstring: one process owns a chip)."""
    if daemon or options:
        import warnings

        warnings.warn("spawn: daemon and extra options are accepted for API "
                      "parity but have no effect on subprocess workers")
    if nprocs < 1:
        nprocs = int(os.environ.get("PADDLE_TRAINERS_NUM", 0)) or (
            os.cpu_count() or 1)
    tmpdir = tempfile.TemporaryDirectory(prefix="paddle_spawn_")
    procs, out_paths = [], []
    mod_dir = None
    mod_name = getattr(func, "__module__", None)
    mod = sys.modules.get(mod_name)
    if mod is not None and getattr(mod, "__file__", None):
        # the child imports func by its dotted module path: walk up one dir
        # per package level so the TOP package's parent lands on sys.path
        mod_dir = os.path.dirname(os.path.abspath(mod.__file__))
        for _ in range(mod_name.count(".")):
            mod_dir = os.path.dirname(mod_dir)
    for rank in range(nprocs):
        in_path = os.path.join(tmpdir.name, f"in_{rank}.pkl")
        out_path = os.path.join(tmpdir.name, f"out_{rank}.pkl")
        with open(in_path, "wb") as f:
            pickle.dump((func, args), f)
        env = dict(os.environ)
        env["PADDLE_TRAINER_ID"] = str(rank)
        env["PADDLE_TRAINERS_NUM"] = str(nprocs)
        if backend is not None:
            env["JAX_PLATFORMS"] = backend
        env["PADDLE_SPAWN_IN"] = in_path
        env["PADDLE_SPAWN_OUT"] = out_path
        # child must import paddle_tpu and func's module by reference
        extra = [p for p in (os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))), mod_dir) if p]
        env["PYTHONPATH"] = os.pathsep.join(
            extra + [env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
        p = subprocess.Popen(
            [sys.executable, "-c",
             "from paddle_tpu.distributed.spawn import _subprocess_main; "
             "_subprocess_main()"],
            env=env)
        procs.append(p)
        out_paths.append(out_path)
    context = ProcessContext(procs, out_paths, tmpdir)
    if join:
        return context.join(timeout)
    return context
