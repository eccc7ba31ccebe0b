"""The decode step outside the paged attention kernel (see
decode_rest_ms.json)."""
from benchmark.harness.span_readers import module_rest_ms as read  # noqa: F401
