"""The network's work count (net_work.py) against a count made by hand
for the gomoku6 configuration's network, and the two network readers on
made-up stretches: a number from spans, program events and counters,
and nothing where a program lacks them."""

import pytest

from perfbench import net_work, spec
from perfbench.test_bench_trace import _ctx, _reader

NET = spec.load_json(spec.config_path("gomoku6"))["net"]


def test_gomoku6_net_by_hand():
    assert NET == {"conv_layers": 2, "channels": 32, "value_hidden": 64,
                   "dtype": "float32"}
    # multiply-adds a row, 36 positions:
    #   conv 3x3 2->32     36 * 9 * 2 * 32  =  20,736
    #   conv 3x3 32->32    36 * 9 * 32 * 32 = 331,776
    #   policy 1x1 32->2   36 * 32 * 2      =   2,304
    #   policy dense       72 * 36          =   2,592
    #   value dense        1152 * 64        =  73,728
    #   value out          64 * 1           =      64
    #                                         431,200 -> 862,400 ops
    assert net_work.ops_per_row(NET) == 862_400
    # weights: 576 + 9,216 + 64 + 2,592 + 73,728 + 64 = 86,240 words
    assert net_work.weights(NET) == 86_240
    # 64 rows: the weights once, 36 words in and 37 out a row
    got = net_work.per_microbatch(NET, 64)
    assert got == {"ops": 64 * 862_400,
                   "bytes": 4 * (86_240 + 64 * 73)}   # 363,648


def test_net_is_bound_by_bytes_on_v5e():
    from perfbench import work

    one = net_work.per_microbatch(NET, 64)
    t, bound = work.least_seconds(one, spec.peaks("TPU v5 lite"))
    assert bound == "bytes" and t == pytest.approx(363_648 / 819e9)


def _net_ctx(spans=(), modules=(), counters=None):
    ctx = _ctx({"devices": {}, "modules": {0: list(modules)}, "host": []},
               counters or {}, spans=spans,
               peaks=spec.peaks("TPU v5 lite"))
    ctx.config = {"net": NET, "sim": {"max_batch": 64}}
    return ctx


def test_net_readers_on_made_up_stretch():
    # 4 supersteps, 3 microbatches: 3 forwards of 2 us on the device
    # among other programs, and 3 finalize spans of 1.5 ms
    modules = [("jit_policy_value_forward(812)", 0, 2_000),
               ("jit_select_arena(1665)", 2_000, 9_000),
               ("jit_policy_value_forward(812)", 10_000, 12_000),
               ("jit_policy_value_forward", 20_000, 22_000),
               ("jit__infer(7)", 30_000, 40_000)]
    spans = [{"name": "sim-finalize", "dur": 1500.0}] * 3 + [
        {"name": "sim-flush", "dur": 400.0},
        {"name": "simulate", "dur": 6000.0}]
    counters = {"service_supersteps_total": 4.0,
                "sim_server_batches_total": 3.0}
    ctx = _net_ctx(spans, modules, counters)
    # 4.5 ms of finalize over 4 supersteps
    assert _reader("net_wait_ms_per_superstep").read(ctx) == \
        pytest.approx(1.125)
    # 3 x 363,648 bytes at 819 GB/s over 6 us of forwards
    assert _reader("net_roofline_pct").read(ctx) == pytest.approx(
        100 * 3 * 363_648 / 819e9 / 6e-6)


def test_net_readers_find_nothing_without_the_programs_names():
    """A program whose forward is jitted under another name, with no
    finalize span: nothing to read; nor without counters or peaks."""
    counters = {"service_supersteps_total": 4.0,
                "sim_server_batches_total": 3.0}
    ctx = _net_ctx([{"name": "simulate", "dur": 6000.0}],
                   [("jit__infer(7)", 0, 2_000)], counters)
    assert _reader("net_wait_ms_per_superstep").read(ctx) is None
    assert _reader("net_roofline_pct").read(ctx) is None
    forward = [("jit_policy_value_forward(812)", 0, 2_000)]
    assert _reader("net_roofline_pct").read(
        _net_ctx(modules=forward)) is None
    ctx = _net_ctx(modules=forward, counters=counters)
    ctx.peaks = None
    assert _reader("net_roofline_pct").read(ctx) is None
