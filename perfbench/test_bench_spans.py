"""The per-layer readers of the program's own spans and counters (move
commit, fused dispatch host time and device wait, host-device bytes), on
a made-up stretch counted by hand, and on what a program without those
spans and counters leaves."""

import pytest

from perfbench.test_bench_trace import _ctx, _reader

NEW = ("commit_ms_per_move", "dispatch_host_ms_per_superstep",
       "device_wait_ms_per_superstep", "host_transfer_mb_per_move")


def _span(name, dur_us):
    return {"name": name, "dur": dur_us}


def test_span_readers_on_made_up_stretch():
    # 4 supersteps of one fused dispatch committing 2 moves
    spans = [_span("fused-dispatch", 100_000.0),
             _span("admit", 1_000.0),
             _span("fused-upload", 4_000.0),
             _span("fused-run", 10_000.0),
             _span("fused-readback", 5_000.0),
             _span("move-commit", 30_000.0), _span("move-commit", 42_000.0),
             _span("commit-snapshot", 17_000.0)]
    counters = {"service_supersteps_total": 4.0,
                "service_moves_committed_total": 2.0,
                "service_host_transfer_bytes_total": 57_400_000.0}
    ctx = _ctx({"devices": {}, "host": []}, counters, spans=spans)
    # 72 ms of commits over 2 moves
    assert _reader("commit_ms_per_move").read(ctx) == pytest.approx(36.0)
    # upload 4 + readback 5 + admit 1 ms over 4 supersteps
    assert _reader("dispatch_host_ms_per_superstep").read(ctx) == \
        pytest.approx(2.5)
    # 10 ms waiting on the device over 4 supersteps
    assert _reader("device_wait_ms_per_superstep").read(ctx) == \
        pytest.approx(2.5)
    # 57.4 MB over 2 moves
    assert _reader("host_transfer_mb_per_move").read(ctx) == \
        pytest.approx(28.7)


def test_span_readers_without_the_programs_spans():
    """A program whose fused dispatch is one span, whose admission and
    commit are instants (absent from the complete spans a context holds)
    and which has no move or byte counters: nothing to read."""
    spans = [_span("fused-dispatch", 100_000.0), _span("tick", 101_000.0)]
    counters = {"service_supersteps_total": 4.0,
                "service_fused_dispatches_total": 1.0}
    ctx = _ctx({"devices": {}, "host": []}, counters, spans=spans)
    for name in NEW:
        assert _reader(name).read(ctx) is None, name
