"""Backward's share of flash attention's device time (see
flash_bwd_share.json)."""
from benchmark.harness.span_readers import op_share as read  # noqa: F401
