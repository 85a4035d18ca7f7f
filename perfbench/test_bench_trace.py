"""The reduction from a trace to the per-layer numbers: on made-up
events counted by hand, and on a short trace recorded on a TPU v5e."""

from types import SimpleNamespace

import pytest

from perfbench import spec, traces


def test_union_and_busy():
    ev = [("a", 0, 10), ("b", 5, 12), ("c", 20, 25), ("d", 21, 22)]
    assert traces.union((s, e) for _, s, e in ev) == [[0, 12], [20, 25]]
    assert traces.busy_ns(ev) == 17.0


def test_idle_gaps_named_by_innermost_annotation():
    ev = [("k", 0, 10), ("k", 30, 40), ("k", 100, 110)]
    host = [("poll", 0, 200), ("result", 50, 90)]
    gaps = traces.idle_gaps(ev, host)
    assert [g[0] for g in gaps] == ["result", "poll"]
    assert [g[1] for g in gaps] == pytest.approx([60e-9, 20e-9])
    assert traces.idle_gaps(ev, [])[0][0] == "between calls"


def test_top_ops():
    ev = [("a", 0, 10), ("b", 10, 15), ("a", 20, 30)]
    got = traces.top_ops(ev)
    assert [g[0] for g in got] == ["a", "b"]
    assert [g[1] for g in got] == pytest.approx([20e-9, 5e-9])


def test_parse_metrics_sums_label_sets():
    text = "\n".join([
        "# HELP service_supersteps_total supersteps executed",
        "# TYPE service_supersteps_total counter",
        'service_supersteps_total{bucket="a"} 10',
        'service_supersteps_total{bucket="b"} 5',
        'sim_server_batch_fill_bucket{le="16"} 3',
        "sim_server_batch_fill_sum 48",
        "sim_server_batch_fill_count 3",
    ])
    got = traces.parse_metrics(text)
    assert got["service_supersteps_total"] == 15
    assert got["sim_server_batch_fill_sum"] == 48
    assert got["sim_server_batch_fill_count"] == 3


def _ctx(trace, counters, shapes=None, peaks=None, spans=(), window=1.0):
    ctx = traces.Context.__new__(traces.Context)
    ctx.trace, ctx.counters, ctx.window_s = trace, counters, window
    ctx.spans, ctx.peaks = list(spans), peaks
    ctx.shapes, ctx.active_slots = shapes, 1
    ctx.config = {"sim": {"max_batch": 64}}
    ctx.log = lambda m: None
    return ctx


def _reader(name):
    return spec.load_module(spec.reader_path(name), "reader")


def test_readers_on_made_up_stretch():
    trace = {"devices": {0: [("fusion.1", 0, 100_000),
                             ("select_arena.9", 200_000, 300_000),
                             ("backup_arena.9", 300_000, 350_000),
                             ("select_arena_copy.1", 0, 50_000)]},
             "modules": {0: [("jit_select_arena(1665)", 190_000, 310_000),
                             ("jit_backup_arena(7520)", 310_000, 360_000),
                             ("jit_dynamic_slice(5899)", 0, 50_000)]},
             "host": []}
    counters = {"service_supersteps_total": 5.0,
                "service_fused_dispatches_total": 2.0,
                "sim_server_batch_fill_sum": 96.0,
                "sim_server_batch_fill_count": 3.0}
    spans = [{"name": "expand", "dur": 2000.0},
             {"name": "select", "dur": 9000.0}]
    ctx = _ctx(trace, counters, spans=spans, window=0.001,
               shapes={"p": 16, "D": 9, "F": 6, "puct": False},
               peaks=spec.peaks("TPU v5 lite"))
    # busy 100 + 150 = 250 us of 1000 us
    assert _reader("device_idle_pct").read(ctx) == pytest.approx(75.0)
    # kernels 150 us over 5 supersteps
    assert _reader("uct_kernel_us_per_superstep").read(ctx) == \
        pytest.approx(30.0)
    # 5 slot-supersteps x 31872 bytes at 819 GB/s over 150 us
    assert _reader("uct_roofline_pct").read(ctx) == pytest.approx(
        100 * 5 * 31872 / 819e9 / 150e-6)
    # in-tree programs 120 + 50 us over 5 supersteps
    assert _reader("intree_device_us_per_superstep").read(ctx) == \
        pytest.approx(34.0)
    assert _reader("supersteps_per_dispatch").read(ctx) == 2.5
    assert _reader("expand_ms_per_superstep").read(ctx) == \
        pytest.approx(0.4)
    assert _reader("sim_batch_fill_pct").read(ctx) == pytest.approx(50.0)


def test_readers_find_nothing_to_read():
    ctx = _ctx({"devices": {}, "host": []}, {},
               shapes={"p": 1, "D": 1, "F": 1, "puct": False})
    for m in spec.benchmark()["per_layer"]:
        assert _reader(m["name"]).read(ctx) is None, m["name"]


RECORDED = spec.HERE / "testdata" / "pong_selfplay.xplane.pb"


def test_recorded_pong_trace():
    """0.4 s of a `pong.selfplay` trace from a TPU v5e (the window's
    events kept, the rest of the file cut away): two fused dispatches of
    four supersteps.  Counted by hand from the file's events: 16 kernel
    events, 8 `select_arena.9` of about 500.8 us and 8 `backup_arena.9`
    of about 115.1 us, 4,927,253 ns in all; the union of all op
    intervals is 29,579,737 ns of the 397,096,748 ns they span."""
    tr = traces.load(str(RECORDED))
    events = tr["devices"][0]
    assert len(tr["devices"]) == 1 and len(events) == 4870
    assert [h[0] for h in tr["host"]] == ["poll"]
    span = max(e[2] for e in events) - min(e[1] for e in events)
    assert span == 397_096_748
    assert traces.busy_ns(events) == 29_579_737
    ctx = _ctx(tr, {"service_supersteps_total": 8.0}, window=span * 1e-9,
               shapes={"p": 16, "D": 9, "F": 6, "puct": False},
               peaks=spec.peaks("TPU v5 lite"))
    ctx.active_slots = 4
    kernels = _reader("uct_kernel_us_per_superstep")
    found = kernels.kernel_events(ctx)
    assert sorted({e[0] for e in found}) == ["backup_arena.9",
                                             "select_arena.9"]
    assert len(found) == 16
    assert sum(e[2] - e[1] for e in found) == 4_927_253
    assert kernels.read(ctx) == pytest.approx(4_927_253e-3 / 8)
    assert _reader("device_idle_pct").read(ctx) == pytest.approx(
        100 * (1 - 29_579_737 / 397_096_748))
    assert _reader("uct_roofline_pct").read(ctx) == pytest.approx(
        100 * (32 * 31872 / 819e9) / 4_927_253e-9)
    assert ctx.breakdown()["device_ops"][0][0] == "while.38"
    # the fused program runs no in-tree program of its own
    assert _reader("intree_device_us_per_superstep").read(ctx) is None
