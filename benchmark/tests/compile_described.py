"""Rehearsal 3, by hand (not a pytest file): compile the new programs of the
benchmark at the REAL sizes for a described, unattached v5e, and print
memory_analysis(). Costs no chip time; nothing runs, so it says nothing about
results or times.

    JAX_PLATFORMS=cpu python benchmark/tests/compile_described.py [reference|serve]
"""
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import json  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from benchmark.models import gpt_reference as ref  # noqa: E402

topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
one = SingleDeviceSharding(topo.devices[0])


def sds(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), tree)


def report(name, lowered):
    t0 = time.time()
    c = lowered.compile()
    m = c.memory_analysis()
    print(f"{name}: compiled in {time.time() - t0:.0f} s; arguments "
          f"{m.argument_size_in_bytes / 1e9:.2f} GB, outputs "
          f"{m.output_size_in_bytes / 1e9:.2f} GB, temporaries "
          f"{m.temp_size_in_bytes / 1e9:.2f} GB", flush=True)


def cfg_of(name):
    return json.load(open(os.path.join(ROOT, "benchmark", "configs", name + ".json")))


def reference():
    cfg = cfg_of("gpt3-medium-355M")
    nh = cfg["num_heads"]
    p = sds(jax.eval_shape(lambda: ref.init_weights(cfg, 0, "float32")))
    ids = jax.ShapeDtypeStruct((2, 1024), jnp.int32, sharding=one)
    report("train reference, value_and_grad over 2 rows of 1,024, float32 highest",
           jax.jit(jax.value_and_grad(
               lambda p, i: ref.loss_fn(p, i, nh))).lower(p, ids))
    cfg = cfg_of("gpt3-xl-1.3B")
    nh = cfg["num_heads"]
    p = sds(jax.eval_shape(lambda: ref.init_weights(cfg, 0, "bfloat16")))
    ids = jax.ShapeDtypeStruct((1408,), jnp.int32, sharding=one)
    pos = jax.ShapeDtypeStruct((128,), jnp.int32, sharding=one)
    report("serve reference, one row of 1,408, logits at 128 positions",
           jax.jit(lambda p, i, q: ref.logits_at(p, i, q, nh)).lower(p, ids, pos))


def serve():
    """The engine's decode program at the 2,560-block pool."""
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import ServingEngine

    cfg = cfg_of("gpt3-xl-1.3B")
    sv = cfg["serve"]
    # a one-layer stand-in holds the program objects; shapes come from the
    # real configuration through eval_shape, nothing is allocated
    g = GPTConfig(vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
                  num_layers=cfg["num_layers"], num_heads=cfg["num_heads"],
                  max_position_embeddings=cfg["max_position_embeddings"],
                  hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    model = GPTForCausalLM(g).bfloat16()
    engine = ServingEngine(model, max_slots=sv["slots"], block_size=sv["block_size"],
                           num_blocks=sv["num_blocks"],
                           prefill_chunk=sv["prefill_chunk"],
                           max_model_len=sv["max_model_len"])
    _, _, pv, bv = engine._functional()
    engine._dev_init()
    toks, tables, lens, temps, seed = engine._dev
    args = sds((pv, bv, toks, engine.pool.layers, tables, lens, temps, seed))
    report(f"decode program, {sv['slots']} slots, pool of {sv['num_blocks']} blocks",
           engine._decode_jit(False).lower(*args))


if __name__ == "__main__":
    which = sys.argv[1:] or ["reference", "serve"]
    for w in which:
        {"reference": reference, "serve": serve}[w]()
