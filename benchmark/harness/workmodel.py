"""What is the chip's and nobody's model: the least time a piece of work
could take on it. The work itself (operations and bytes, from shapes and
live context) is counted by the model family's own
benchmark/models/<family>_work.py; the contract is in manifest.py."""
from __future__ import annotations


def roofline_seconds(work: dict, peaks: dict):
    """The least time the chip could take, and which bound gives it."""
    t_f = work["flops"] / peaks["flops_per_s"]
    t_b = work["bytes"] / peaks["bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")
