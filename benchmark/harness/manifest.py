"""BENCHMARK.json and the data files it names. Everything that belongs to one
configuration, one traffic mix or one per-layer metric is a file of its own,
found by the name in BENCHMARK.json: a later PR adds files and entries and
edits nothing here.

A model family (a configuration's "models": "<family>") is three modules
under benchmark/models/, which `models()` finds by name:

- <family>_reference.py: the plain reference and the seeded weights;
  imports nothing of the program.
- <family>_program.py: builds the system under test from the configuration.
- <family>_work.py: the operations and bytes the family's work needs. Plain
  arithmetic on the configuration file and on facts; imports nothing of the
  program. The drivers call only these three, and hand the same facts to
  every per-layer reader in `ctx`:
    served_flops(cfg, requests) -> forward operations of a serving window,
      from one (prompt_tokens, prefix_matched, tokens_at_close) a request;
    traced_work(cfg, facts) -> {work name: {"flops", "bytes"}}, the work of
      the traced period by kernel or step. Serving facts: "decode_contexts"
      (the live context of every token decoded in the period),
      "prefill_tokens" and "ticks" (the engine's counts over the same
      period). Training facts: "batch", "sequence", "steps" traced;
    train_flops_per_token(cfg, sequence) -> forward and backward operations
      a trained token needs.
  A reader finds a work by the name its layer_metrics/<metric>.json gives
  ("work": "paged_attention"); a family that has no such work leaves the
  name out and the metric stays silent there.

`ctx`, which every reader gets: "counters", "clocks", "trace" (the reduced
trace), "work" (traced_work's), "facts", "requests" (the triples above; a
training cell has none), "config" (the configuration file), "peaks",
"chips"."""
from __future__ import annotations

import importlib
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load(path):
    with open(path) as f:
        return json.load(f)


def benchmark():
    return _load(os.path.join(ROOT, "BENCHMARK.json"))


def cell(name: str, bench=None) -> dict:
    """One cell with its configuration, traffic mix and metric entries."""
    bench = bench or benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]

    def mine(m, every_cell_default):
        return name in m["workloads"] if "workloads" in m else every_cell_default

    e2e = [m for m in bench["end_to_end"] if mine(m, True)]
    mine_e2e = {m["name"] for m in e2e}
    return {
        "name": name, "chips": int(w["chips"]),
        "config_name": w["config"], "traffic_name": w["traffic"],
        "config": _load(os.path.join(ROOT, cfg_entry["file"])),
        "traffic": traffic(w["traffic"]),
        "end_to_end": e2e,
        "per_layer": [m for m in bench["per_layer"]
                      if mine(m, m["moves"] in mine_e2e)],
    }


def traffic(name: str) -> dict:
    return _load(os.path.join(BENCH_DIR, "traffic", name + ".json"))


def layer_metric(name: str):
    """(spec, reader module or None) of one per-layer metric: its
    layer_metrics/<name>.json, and a reader of its own beside it if it has
    one (layer_metrics/<name>.py with read(ctx, spec))."""
    base = os.path.join(BENCH_DIR, "layer_metrics", name)
    spec = _load(base + ".json")
    mod = None
    if os.path.exists(base + ".py"):
        s = importlib.util.spec_from_file_location(
            "layer_metric_" + name.replace(".", "_").replace("-", "_"),
            base + ".py")
        mod = importlib.util.module_from_spec(s)
        s.loader.exec_module(mod)
    return spec, mod


def models(name: str):
    """(reference, program, work) modules of a model family: see the top of
    this file for what each holds."""
    return tuple(importlib.import_module(f"benchmark.models.{name}_{part}")
                 for part in ("reference", "program", "work"))
