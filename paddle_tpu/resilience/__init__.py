"""Fault-tolerant training runtime.

Pieces (wired together by ResilientTrainer, each usable alone):
  - CheckpointManager  : crash-consistent commit (tmp dir -> manifest with
                         per-array checksums -> atomic rename), keep-last-N
                         GC that never drops the last valid checkpoint, and
                         restore_latest() with corruption fallback.
  - PreemptionHandler  : SIGTERM/SIGINT + elastic-membership loss latched
                         into one flag the training loop polls.
  - RetryPolicy        : backoff/jitter/deadline retries, adopted by the
                         TCPStore connect, collective-store init, and the
                         DataLoader worker respawn path.
  - chaos              : fault-injection harness (crash points inside
                         checkpoint writes, NaN batch poisoning, worker
                         kills, fake preemption signals) backing the tests.
"""
from __future__ import annotations

from . import chaos  # noqa: F401
from .checkpoint_manager import (  # noqa: F401
    CheckpointCorrupt, CheckpointManager, RestoredCheckpoint,
)
from .preemption import PreemptionHandler  # noqa: F401
from .retry import RetryError, RetryPolicy, retrying  # noqa: F401

__all__ = [
    "CheckpointManager", "CheckpointCorrupt", "RestoredCheckpoint",
    "PreemptionHandler", "RetryPolicy", "RetryError", "retrying",
    "ResilientTrainer", "ElasticTrainer", "MicroBatchRebalancer", "chaos",
]


def __getattr__(name):
    # ResilientTrainer / ElasticTrainer pull in jit.trainer (and with it
    # the whole nn/opt stack); resolve them lazily so
    # `from paddle_tpu.resilience import chaos` stays import-light for
    # forked dataloader workers.
    if name == "ResilientTrainer":
        from .trainer import ResilientTrainer

        return ResilientTrainer
    if name in ("ElasticTrainer", "MicroBatchRebalancer"):
        from . import elastic

        return getattr(elastic, name)
    raise AttributeError(name)
