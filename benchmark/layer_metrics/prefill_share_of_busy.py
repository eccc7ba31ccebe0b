"""Share of device time that goes to admitting requests (see
prefill_share_of_busy.json)."""
from benchmark.harness.span_readers import module_share_of_busy as read  # noqa: F401
