"""Observability layer: tracer semantics, metrics format, service wiring.

Claim groups:

  * tracer — per-track LIFO nesting is enforced (out-of-order end
    asserts), nested spans export with child intervals inside parents
    (pinned with an injectable fake clock), the ring drops oldest,
    async begin/end pairs carry their id through;
  * metrics — Prometheus exposition format (# HELP / # TYPE, label
    escaping, cumulative histogram buckets with the +Inf closer),
    get-or-create sharing, kind conflicts raise, the null registry is
    inert;
  * service wiring — a 3-bucket heterogeneous run under the
    weighted-queue-depth gang tick with compaction enabled exports
    valid Chrome-trace JSON covering all six superstep phases and the
    full request lifecycle (submit -> result and submit -> evict), and
    client.metrics() renders the scheduler/pool telemetry;
  * fused path — a K=4 fused run splits each fused-dispatch into
    fused-upload / fused-run / fused-readback (plus admit and
    move-commit) and each move-commit into commit-snapshot /
    commit-emit / commit-reroot / commit-write, the children cover
    their parents, the transfer counters count the bytes the shapes
    give, and the spans reach the JAX profiler's host plane.
"""

import json

import pytest

from repro.core import TreeConfig
from repro.envs import BanditTreeEnv, BanditValueBackend
from repro.obs import (
    NULL_REGISTRY, NULL_TRACER, MetricsRegistry, Tracer,
)
from repro.service import SearchClient, SearchRequest

PHASES = ("select", "expand", "simulate", "backup",
          "compact-gather", "compact-scatter")


def _fake_clock(step_ns: int = 1000):
    t = [0]

    def clk():
        t[0] += step_ns
        return t[0]
    return clk


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_nested_spans_export_child_inside_parent():
    tr = Tracer(clock_ns=_fake_clock())
    tid = tr.track("main")
    with tr.span("outer", cat="phase", tid=tid):
        with tr.span("inner", cat="phase", tid=tid, rows=3):
            pass
    ev = tr.events()
    # inner closes first, so it is recorded first
    assert [e["name"] for e in ev] == ["inner", "outer"]
    inner, outer = ev
    assert inner["ts"] >= outer["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    assert inner["args"] == {"rows": 3}
    assert all(e["ph"] == "X" for e in ev)
    assert tr.open_depth(tid) == 0


def test_out_of_order_end_asserts():
    tr = Tracer()
    a = tr.begin("a")
    tr.begin("b")
    with pytest.raises(AssertionError):
        tr.end(a)


def test_tracks_are_independent_stacks():
    tr = Tracer(clock_ns=_fake_clock())
    t0, t1 = tr.track("sched"), tr.track("pool")
    assert t0 != t1
    a = tr.begin("tick", tid=t0)
    b = tr.begin("superstep", tid=t1)
    tr.end(a)          # legal: different track than b
    tr.end(b)
    assert [e["tid"] for e in tr.events()] == [t0, t1]


def test_ring_drops_oldest():
    tr = Tracer(capacity=4, clock_ns=_fake_clock())
    for i in range(10):
        tr.instant(f"i{i}")
    ev = tr.events()
    assert [e["name"] for e in ev] == ["i6", "i7", "i8", "i9"]
    assert tr.dropped == 6
    tr.clear()
    assert tr.events() == [] and tr.dropped == 0


def test_async_pairs_and_export_schema(tmp_path):
    tr = Tracer(clock_ns=_fake_clock())
    tr.track("main")
    tr.async_begin("request", 7, cat="request", uid=7)
    tr.instant("admit", cat="request", uid=7)
    tr.async_end("request", 7, cat="request", status="done")
    path = tmp_path / "trace.json"
    out = tr.export(str(path))
    loaded = json.loads(path.read_text())
    assert loaded == json.loads(json.dumps(out))
    evs = loaded["traceEvents"]
    # metadata first: process + thread naming for the viewer
    assert evs[0]["ph"] == "M" and evs[0]["name"] == "process_name"
    pair = [e for e in evs if e["ph"] in "be"]
    assert [e["ph"] for e in pair] == ["b", "e"]
    assert all(e["id"] == 7 for e in pair)
    assert all("ts" in e and "pid" in e and "tid" in e for e in pair)


def test_export_coerces_exotic_arg_values(tmp_path):
    import numpy as np
    tr = Tracer(clock_ns=_fake_clock())
    tr.instant("x", rows=np.int32(5), frac=np.float64(0.5), tag=object())
    out = tr.export()
    json.dumps(out)    # must not raise
    args = out["traceEvents"][-1]["args"]
    assert args["rows"] == 5 and args["frac"] == 0.5
    assert isinstance(args["tag"], str)


def test_null_tracer_is_inert():
    assert not NULL_TRACER.enabled
    tok = NULL_TRACER.begin("x")
    NULL_TRACER.end(tok)
    with NULL_TRACER.span("y"):
        pass
    NULL_TRACER.instant("z")
    assert NULL_TRACER.events() == []
    assert NULL_TRACER.export() == {"traceEvents": [],
                                    "displayTimeUnit": "ms"}


def test_tracer_rejects_bad_capacity():
    with pytest.raises(ValueError):
        Tracer(capacity=0)


def test_end_args_join_begin_args():
    tr = Tracer(clock_ns=_fake_clock())
    tok = tr.begin("move-commit", uid=3)
    tr.end(tok, last=True)
    assert tr.events()[0]["args"] == {"uid": 3, "last": True}


def test_export_reports_dropped_events():
    tr = Tracer(capacity=2, clock_ns=_fake_clock())
    for i in range(5):
        tr.instant(f"i{i}")
    assert tr.export()["otherData"] == {"dropped": 3}


def test_spans_on_two_tracks_end_in_any_order_under_the_profiler(tmp_path):
    """Each open span holds its own profiler annotation, so spans on
    different tracks may close out of order while a profile records."""
    import jax

    tr = Tracer()
    t0, t1 = tr.track("pool:a"), tr.track("pool:a:gang1")
    with jax.profiler.trace(str(tmp_path)):
        a = tr.begin("fused-dispatch", tid=t0)
        b = tr.begin("overlap-wait-device", tid=t1)
        assert a.note is not None and a.note is not b.note
        tr.end(a)
        tr.end(b)
    assert [e["name"] for e in tr.events()] == ["fused-dispatch",
                                                "overlap-wait-device"]


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_prometheus_render_format():
    reg = MetricsRegistry()
    c = reg.counter("foo_total", "things done", bucket="a")
    c.inc()
    c.inc(2)
    g = reg.gauge("bar")
    g.set(5)
    g.dec()
    text = reg.render()
    lines = text.splitlines()
    assert "# HELP foo_total things done" in lines
    assert "# TYPE foo_total counter" in lines
    assert 'foo_total{bucket="a"} 3' in lines
    assert "# TYPE bar gauge" in lines
    assert "bar 4" in lines
    # get-or-create: same (name, labels) -> same series
    assert reg.counter("foo_total", bucket="a") is c
    assert reg.get("foo_total", bucket="a").value == 3


def test_histogram_buckets_are_cumulative():
    reg = MetricsRegistry()
    h = reg.histogram("lat", buckets=(1, 2, 4))
    for v in (1, 3, 9):
        h.observe(v)
    lines = reg.render().splitlines()
    assert 'lat_bucket{le="1"} 1' in lines
    assert 'lat_bucket{le="2"} 1' in lines
    assert 'lat_bucket{le="4"} 2' in lines
    assert 'lat_bucket{le="+Inf"} 3' in lines
    assert "lat_sum 13" in lines
    assert "lat_count 3" in lines
    snap = reg.snapshot()
    assert snap["lat"]['lat_bucket{le="+Inf"}'] == 3


def test_kind_conflict_raises():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(ValueError):
        reg.gauge("x")


def test_label_values_are_escaped():
    reg = MetricsRegistry()
    reg.counter("esc_total", tag='a"b\\c\nd').inc()
    line = [ln for ln in reg.render().splitlines()
            if ln.startswith("esc_total")][0]
    assert line == 'esc_total{tag="a\\"b\\\\c\\nd"} 1'


def test_null_registry_is_inert():
    assert not NULL_REGISTRY.enabled
    m = NULL_REGISTRY.counter("anything", bucket="x")
    m.inc()
    m.observe(3)
    m.set(1)
    assert NULL_REGISTRY.render() == ""
    assert NULL_REGISTRY.snapshot() == {}
    assert NULL_REGISTRY.get("anything") is None


# ---------------------------------------------------------------------------
# service wiring: 3 heterogeneous buckets, all phases + full lifecycle
# ---------------------------------------------------------------------------

def test_three_bucket_run_exports_phases_and_lifecycle():
    env = BanditTreeEnv(fanout=3, terminal_depth=12)
    cfgs = [TreeConfig(X=96, F=3, D=5), TreeConfig(X=64, F=3, D=4),
            TreeConfig(X=48, F=3, D=6)]
    cl = SearchClient(
        env, BanditValueBackend(), G=4, p=4, default_cfg=cfgs[0],
        policy="weighted-queue-depth", compact_threshold=0.7,
        trace=True, metrics=True)
    for i in range(6):
        cl.submit(SearchRequest(uid=i, seed=i, budget=3, moves=2,
                                cfg=cfgs[i % 3]))
    doomed = cl.submit(SearchRequest(uid=99, seed=7, budget=64),
                       deadline_supersteps=0)
    cl.drain()
    assert doomed.status() == "evicted"

    trace = cl.trace_export()
    json.dumps(trace)                      # valid Chrome-trace JSON
    evs = trace["traceEvents"]
    names = {e["name"] for e in evs}
    for phase in PHASES:
        assert phase in names, f"phase {phase!r} missing from trace"
    # request lifecycle: async b/e pairs for a completed and an evicted
    # request, with the connecting instants in between
    begun = {e["id"] for e in evs if e.get("ph") == "b"}
    ended = {e["id"]: e for e in evs if e.get("ph") == "e"}
    assert 0 in begun and ended[0]["args"]["status"] == "done"
    assert 99 in begun and ended[99]["args"]["status"] == "evicted"
    assert {"submit", "admit", "move-commit", "evict"} <= names
    # every pool got its own named track, plus the scheduler's
    tracks = {e["args"]["name"] for e in evs
              if e.get("ph") == "M" and e["name"] == "thread_name"}
    assert "scheduler" in tracks
    assert sum(t.startswith("pool:") for t in tracks) == 3

    text = cl.metrics()
    assert "service_supersteps_total" in text
    assert "service_smoothed_load" in text
    assert "service_admitted_total" in text
    assert 'reason="deadline"' in text
    snap = cl.registry.snapshot()
    assert any(k.startswith("service_queue_depth") for k in snap)
    cl.close()


# ---------------------------------------------------------------------------
# fused path: the dispatch's and the commit's inner spans, transfer bytes
# ---------------------------------------------------------------------------

FUSED_CFG = TreeConfig(X=256, F=4, D=5)
FUSED_G = 2


def _fused_client(**obs):
    return SearchClient(
        BanditTreeEnv(fanout=4, terminal_depth=8), BanditValueBackend(),
        G=FUSED_G, p=4, executor="faithful", default_cfg=FUSED_CFG,
        supersteps_per_dispatch=4, **obs)


def _serve(cl, n=3):
    handles = [cl.submit(SearchRequest(uid=i, seed=i, budget=4, moves=3))
               for i in range(n)]
    return {h.uid: h.result() for h in handles}


def _direct_children(spans, parent):
    """Spans on the parent's track inside it and inside no other one."""
    inside = [c for c in spans if c is not parent
              and c["tid"] == parent["tid"]
              and parent["ts"] <= c["ts"]
              and c["ts"] + c["dur"] <= parent["ts"] + parent["dur"]]
    return [c for c in inside if not any(
        o is not c and o["ts"] <= c["ts"]
        and c["ts"] + c["dur"] <= o["ts"] + o["dur"] for o in inside)]


def test_fused_run_splits_dispatch_and_commit_into_spans():
    """The spans run on the process's CPU clock with the garbage
    collector held off, so the share of a parent its children cover is
    the program's own: time the test runner's other processes take from
    this one, or a collection of what earlier tests left, opens no gap
    between spans."""
    import dataclasses
    import gc
    import time

    from repro.core.tree import init_arena, unpack_tree

    cl = _fused_client(trace=Tracer(clock_ns=time.process_time_ns),
                       metrics=MetricsRegistry())
    gc.collect()
    gc.disable()
    try:
        done = _serve(cl)
    finally:
        gc.enable()
    trace = cl.trace_export()
    reg, stats = cl.registry, cl.stats
    cl.close()
    plain = _fused_client()
    assert {u: (r.actions, [list(v) for v in r.visit_counts])
            for u, r in _serve(plain).items()} == \
        {u: (r.actions, [list(v) for v in r.visit_counts])
         for u, r in done.items()}
    plain.close()

    spans = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    kids = {"fused-dispatch": {"admit", "fused-upload", "fused-run",
                               "fused-readback", "move-commit"},
            "move-commit": {"commit-snapshot", "commit-emit",
                            "commit-reroot", "commit-write"}}
    for parent_name, allowed in kids.items():
        parents = [e for e in spans if e["name"] == parent_name]
        assert parents, parent_name
        covered = 0.0
        seen = set()
        for parent in parents:
            children = _direct_children(spans, parent)
            assert {c["name"] for c in children} <= allowed, parent_name
            seen |= {c["name"] for c in children}
            covered += sum(c["dur"] for c in children)
        assert seen == allowed, (parent_name, seen)
        assert covered >= 0.9 * sum(p["dur"] for p in parents), parent_name
    for parent in (e for e in spans if e["name"] == "fused-dispatch"):
        names = [c["name"] for c in sorted(_direct_children(spans, parent),
                                           key=lambda c: c["ts"])]
        run = [n for n in names if n.startswith("fused-")]
        assert run == ["fused-upload", "fused-run", "fused-readback"]
    last = [e["args"]["last"] for e in spans if e["name"] == "move-commit"]
    assert last.count(True) == len(done)

    # transfer bytes from the shapes: per dispatch the state image (and
    # budgets) up, the states and two size rows down; per move the rows
    # covering one slot's tree down, per re-rooted move up (here all
    # X=256 rows: the smallest bucket is larger), with size and root but
    # never the log table
    label = f"X{FUSED_CFG.X}_D{FUSED_CFG.D}_Fp{FUSED_CFG.Fp}"
    moved = {site: reg.get("service_host_transfer_bytes_total",
                           bucket=label, site=site, dir=d).value
             for site, d in (("upload", "h2d"), ("readback", "d2h"),
                             ("snapshot", "d2h"), ("write", "h2d"))}
    image = FUSED_G * FUSED_CFG.X * 8 * 4      # state (8,) float32
    fresh = unpack_tree(init_arena(FUSED_CFG, 1))
    slot = sum(a.nbytes for k, a in dataclasses.asdict(fresh).items()
               if k != "log_table")
    dispatches = stats.fused_dispatches
    moves = reg.get("service_moves_committed_total", bucket=label).value
    rerooted = sum(e["args"]["reuse"] for e in spans
                   if e["name"] == "commit-reroot")
    assert stats.fused_escape_expand == 0
    assert moves == sum(len(r.actions) for r in done.values()) == 9
    assert moved == {"upload": dispatches * (image + FUSED_G * 4),
                     "readback": dispatches * (image + 2 * FUSED_G * 4),
                     "snapshot": moves * slot,
                     "write": rerooted * slot}
    assert rerooted == moves - len(done)


def test_spans_reach_the_profilers_host_plane(tmp_path):
    """Under jax.profiler.trace the client's spans are host events of
    the profile, on the device's clock, as a traced benchmark run
    records them."""
    import jax

    from perfbench import traces

    cl = _fused_client(trace=Tracer())
    with jax.profiler.trace(str(tmp_path),
                            profiler_options=traces.profile_options()):
        _serve(cl, n=2)
    cl.close()
    (path,) = list(tmp_path.rglob("*.xplane.pb"))
    host = [ev.name for plane in jax.profiler.ProfileData.from_file(
        str(path)).planes if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events]
    for name in ("fused-dispatch", "fused-upload", "fused-run",
                 "fused-readback", "move-commit", "commit-snapshot"):
        assert name in host, name
