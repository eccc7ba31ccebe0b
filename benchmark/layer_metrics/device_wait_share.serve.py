"""Share of the engine tick spent waiting for the device (see
device_wait_share.serve.json)."""
from benchmark.harness.span_readers import child_share as read  # noqa: F401
