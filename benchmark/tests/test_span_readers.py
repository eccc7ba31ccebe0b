"""The readers that stand on the program's spans and names, each on a
hand-made ring and a hand-made reduced trace: exact values, and None where
there is nothing to read (an empty ring, an empty trace, the names of the
parent commit)."""
import pytest

from benchmark.harness import manifest, readers, span_readers

MS = 1_000_000


def span(name, sid, parent, begin_ms, end_ms):
    return {"name": name, "id": sid, "parent": parent, "cat": "serving",
            "begin_ns": begin_ms * MS, "end_ns": end_ms * MS, "tid": 1}


# three ticks of 100, 110 and 50 ms; the fetches inside them take 90, 95 + 5
# and none; one fetch has no tick (its tick began before the trace did)
RING = [
    span("serving.schedule", 2, 1, 0, 1),
    span("serving.decode", 3, 1, 1, 4),
    span("serving.fetch", 4, 1, 5, 95),
    span("serving.tick", 1, None, 0, 100),
    span("serving.fetch", 6, 5, 100, 195),
    span("serving.fetch", 7, 5, 200, 205),
    span("serving.tick", 5, None, 100, 210),
    span("serving.tick", 8, None, 210, 260),
    span("serving.fetch", 9, None, 300, 350),
    span("serving.queue", 10, None, 0, 50),
]

TRACE = {
    "window_s": 6.0, "busy_s": 5.0,
    "modules": {"jit_step(11)": [0.100, 0.102, 0.104],
                "jit_serve_prefill(12)": [0.05, 0.07],
                "jit_serve_scatter(13)": [0.01],
                "jit_serve_clear_slot(14)": [0.02],
                "jit_train_step(15)": [0.3, 0.31, 0.32],
                "jit_convert_element_type(16)": [0.001]},
    "ops": {"%paged_decode.1 = (f32[8,16,1,1,128]) custom-call(...)": 0.06,
            "%paged_decode.2 = (f32[8,16,1,1,128]) custom-call(...)": 0.09,
            "%flash_fwd.3 = (bf16[128,1024,64]) custom-call(...)": 0.25,
            "%flash_bwd_dq.4 = bf16[128,1024,64] custom-call(...)": 0.35,
            "%flash_bwd_dkv.5 = (bf16[128,1024,64]) custom-call(...)": 0.40,
            "%fusion.6 = bf16[8,2048] fusion(...)": 1.0},
}
# what the parent of the PR that named things shows: nothing of the above
OLD_NAMES = {
    "window_s": 6.0, "busy_s": 5.0,
    "modules": {"jit_step(11)": [0.1], "jit_pf(12)": [0.05]},
    "ops": {"%step.30 = (f32[8,16,1,1,128]) custom-call(...)": 0.1,
            "%branch_0_fun.7 = (bf16[128,1024,64]) custom-call(...)": 0.2},
}


def read(name, ctx):
    spec, mod = manifest.layer_metric(name)
    return mod.read(ctx, spec)


@pytest.fixture
def ring(monkeypatch):
    def put(spans):
        monkeypatch.setattr(span_readers, "ring",
                            lambda ctx: list(spans) if ctx.get("trace") else [])
    put(RING)
    return put


def test_tick_host_ms_is_the_tick_less_its_fetch_children(ring):
    # 100 - 90, 110 - 100, 50 - 0 -> median 10
    assert read("tick_host_ms", {"trace": TRACE}) == pytest.approx(10.0)


def test_device_wait_share_counts_only_fetches_inside_a_tick(ring):
    # (90 + 95 + 5) of (100 + 110 + 50); the fetch with no tick is left out
    assert read("device_wait_share.serve", {"trace": TRACE}) == \
        pytest.approx(100.0 * 190 / 260)


@pytest.mark.parametrize("name", ["tick_host_ms", "device_wait_share.serve"])
def test_span_metrics_read_nothing_from_an_empty_ring(ring, name):
    ring([])
    assert read(name, {"trace": TRACE}) is None


def test_spans_without_ids_are_not_read(monkeypatch):
    """The ring of the parent commit has neither `id` nor `parent`."""
    from paddle_tpu.observability import spans

    monkeypatch.setattr(spans, "since", lambda mark: [
        {k: v for k, v in s.items() if k not in ("id", "parent")}
        for s in RING])
    assert span_readers.ring({"trace": TRACE}) == []
    assert read("tick_host_ms", {"trace": TRACE}) is None


def test_the_ring_is_read_only_in_a_traced_run():
    from paddle_tpu.observability import spans

    spans.clear()
    spans.record_span("serving.tick", 0, 5 * MS)
    try:
        assert span_readers.ring({"trace": {}}) == []
        assert span_readers.ring({}) == []
        got = span_readers.ring({"trace": TRACE})
        assert [s["name"] for s in got] == ["serving.tick"]
        assert got[0]["id"] == 1 and got[0]["parent"] is None
    finally:
        spans.clear()


def test_decode_rest_ms_is_the_module_less_the_kernel():
    # median 102 ms, less (60 + 90) ms of kernel over 3 modules
    assert read("decode_rest_ms", {"trace": TRACE}) == pytest.approx(52.0)


def test_prefill_share_of_busy_sums_the_admission_modules():
    # (50 + 70 + 10 + 20) ms of 5 s busy
    assert read("prefill_share_of_busy", {"trace": TRACE}) == \
        pytest.approx(100.0 * 0.15 / 5.0)


def test_flash_bwd_share():
    assert read("flash_bwd_share", {"trace": TRACE}) == \
        pytest.approx(100.0 * 0.75 / 1.0)


def test_the_data_only_metrics_find_the_new_names():
    ctx = {"trace": TRACE, "peaks": {"flops_per_s": 1e12, "bytes_per_s": 1e9},
           "work": {"paged_attention": {"flops": 0.0, "bytes": 3e6}}}
    got = readers.read_all(
        [{"name": "train_step_device_ms", "unit": "ms"},
         {"name": "paged_decode_roofline", "unit": "%"}], ctx)
    assert got["train_step_device_ms"]["value"] == pytest.approx(310.0)
    # 3 MB at 1 GB/s is 3 ms, of 150 ms of kernel time
    assert got["paged_decode_roofline"]["value"] == pytest.approx(2.0)


NEW = ["tick_host_ms", "device_wait_share.serve", "decode_rest_ms",
       "prefill_share_of_busy", "paged_decode_roofline", "flash_bwd_share",
       "train_step_device_ms"]


@pytest.mark.parametrize("trace", [{}, OLD_NAMES], ids=["empty", "old_names"])
def test_nothing_to_read_gives_none_and_never_zero(ring, trace):
    ring([])
    ctx = {"trace": trace, "peaks": {"flops_per_s": 1e12, "bytes_per_s": 1e9},
           "work": {"paged_attention": {"flops": 0.0, "bytes": 3e6}},
           "counters": {}, "clocks": {}, "chips": 1}
    entries = [m for m in manifest.benchmark()["per_layer"] if m["name"] in NEW]
    assert len(entries) == len(NEW)
    assert readers.read_all(entries, ctx) == {}
