"""Sparse-vs-dense benchmark (VERDICT r5 item 5): when does the COO
sparse conv path beat dense-masked convolution?

Reference process model: the reference justifies its sparse kernels
(paddle/phi/kernels/sparse/) on high-sparsity 3D workloads (point
clouds); this bench measures the same trade-off for the TPU-native
site-table formulation at several sparsity levels and writes one JSON
artifact. With SPARSEBENCH_TPU=1 it runs on whatever platform jax finds;
otherwise it records backend=cpu (relative numbers still rank the
crossover).

Usage: python tools/sparsebench.py [--out SPARSEBENCH_r05.json]
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if os.environ.get("SPARSEBENCH_TPU") != "1":   # a CPU tool by default
    os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _sync(x):
    import jax

    jax.block_until_ready(x)
    return x


def bench_one(sparsity, spatial=(32, 32, 32), c_in=16, c_out=32, k=3,
              steps=5):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.sparse import SparseCooTensor
    from paddle_tpu.sparse.conv import sparse_conv

    rng = np.random.RandomState(0)
    vol = int(np.prod(spatial))
    nnz = max(1, int(vol * (1.0 - sparsity)))
    flat = rng.choice(vol, nnz, replace=False)
    coords = np.stack(np.unravel_index(flat, spatial))
    idx = np.concatenate([np.zeros((1, nnz), np.int64), coords]).astype(np.int32)
    vals = rng.randn(nnz, c_in).astype(np.float32)
    w = jnp.asarray(rng.randn(k, k, k, c_in, c_out).astype(np.float32) * 0.1)

    x_sp = SparseCooTensor(jnp.asarray(idx), jnp.asarray(vals),
                           (1,) + spatial + (c_in,))
    dense = jnp.asarray(np.asarray(x_sp.to_dense()))

    # sparse path (jit over fixed nnz)
    def sp_fn(values):
        xx = SparseCooTensor(jnp.asarray(idx), values,
                             (1,) + spatial + (c_in,))
        return sparse_conv(xx, w, stride=1, padding=1)._values

    sp_jit = jax.jit(sp_fn)
    _sync(sp_jit(jnp.asarray(vals)))
    t0 = time.perf_counter()
    for _ in range(steps):
        out = sp_jit(jnp.asarray(vals))
    _sync(out)
    t_sparse = (time.perf_counter() - t0) / steps

    # dense-masked path: plain conv on the dense volume (the masked-out
    # sites are zeros; XLA computes them anyway — that's the comparison)
    dn = jnp.transpose(dense, (0, 4, 1, 2, 3))  # NCDHW
    wd = jnp.transpose(w, (4, 3, 0, 1, 2))      # OIDHW

    def dn_fn(xv):
        return jax.lax.conv_general_dilated(
            xv, wd, (1, 1, 1), "SAME",
            dimension_numbers=("NCDHW", "OIDHW", "NCDHW"))

    dn_jit = jax.jit(dn_fn)
    _sync(dn_jit(dn))
    t0 = time.perf_counter()
    for _ in range(steps):
        outd = dn_jit(dn)
    _sync(outd)
    t_dense = (time.perf_counter() - t0) / steps

    return {"sparsity": sparsity, "nnz": nnz,
            "sparse_ms": round(t_sparse * 1e3, 3),
            "dense_ms": round(t_dense * 1e3, 3),
            "speedup": round(t_dense / t_sparse, 3)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(_REPO,
                                                  "SPARSEBENCH_r05.json"))
    args = ap.parse_args()
    import jax

    rows = [bench_one(s) for s in (0.999, 0.99, 0.95, 0.9, 0.5)]
    for r in rows:
        print(r)
    report = {"backend": jax.default_backend(),
              "ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
              "shape": "1x32^3", "kernel": 3, "rows": rows,
              "crossover": min((r["sparsity"] for r in rows
                                if r["speedup"] > 1.0), default=None)}
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    print(f"-> {os.path.basename(args.out)} (backend={report['backend']}, "
          f"sparse wins at sparsity >= {report['crossover']})")


if __name__ == "__main__":
    main()
