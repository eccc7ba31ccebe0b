"""The engine tick's own time on the host (see tick_host_ms.json)."""
from benchmark.harness.span_readers import self_ms as read  # noqa: F401
