"""SearchClient handle API + global scheduler core.

Claim groups:

  * handle lifecycle — submit returns an opaque handle; cancel works
    before admission, mid-flight (committed moves survive) and is a
    no-op after completion; deadline budgets evict queued and in-flight
    requests; streamed moves() is bit-identical to the terminal trace;
  * scheduling — priorities reorder admission, every policy returns
    bit-identical per-request results (policies move WHEN work happens,
    never WHAT it computes), the weighted-queue-depth gang tick fuses
    one evaluate() batch across pools strictly larger than any single
    pool's, and fused vs per-pool evaluation is bit-identical;
  * retirement — idle pools release their arena after the TTL and are
    resurrected on demand, preserving every per-request result;
  * stats / exports — the monotonic ticks clock and admission-wait
    histogram survive aggregation; the package exports one surface.
"""

import numpy as np
import pytest

from repro.core import TreeConfig
from repro.core.tree import bucket_key
from repro.envs import BanditTreeEnv, BanditValueBackend
from repro.service import (
    POLICY_NAMES, ArenaPool, MoveEvent, SearchClient, SearchRequest,
)

ENV = BanditTreeEnv(fanout=3, terminal_depth=12)
P = 4

CFG_A = TreeConfig(X=96, F=3, D=5)
CFG_B = TreeConfig(X=64, F=3, D=4)
CFG_C = TreeConfig(X=48, F=3, D=6)
MIX = [CFG_A, CFG_B, CFG_C, CFG_A, CFG_B, CFG_C]


def _client(**kw):
    kw.setdefault("G", 2)
    kw.setdefault("p", P)
    kw.setdefault("default_cfg", CFG_A)
    return SearchClient(ENV, BanditValueBackend(), **kw)


def _assert_result_equal(got, want, label):
    assert got.actions == want.actions, label
    assert got.rewards == want.rewards, label
    assert got.supersteps == want.supersteps, label
    for va, vb in zip(got.visit_counts, want.visit_counts):
        np.testing.assert_array_equal(va, vb, err_msg=label)


# ---------------------------------------------------------------------------
# handle lifecycle
# ---------------------------------------------------------------------------

def test_submit_returns_handle_not_pool():
    cl = _client()
    h = cl.submit(SearchRequest(uid=0, seed=0, budget=2))
    assert not h.done()
    assert h.status() == "queued"
    res = h.result()                      # polls to completion
    assert h.done() and h.status() == "done"
    assert res.uid == 0 and res.actions and not res.cancelled
    assert "uid=0" in repr(h)
    cl.close()


def test_poll_budget_and_run_until():
    cl = _client()
    h = cl.submit(SearchRequest(uid=0, seed=1, budget=4))
    assert cl.poll(0) == 0                # no budget, no work done
    assert cl.poll(1) == 1                # one tick
    assert not h.done()
    assert cl.run_until(lambda c: h.done())
    assert cl.poll(5) == 0                # drained
    assert cl.run_until(lambda c: False, max_ticks=3) is False
    cl.close()


def test_handle_lookup_and_duplicate_uid_rejected():
    cl = _client()
    h = cl.submit(SearchRequest(uid=7, seed=0, budget=2))
    assert cl.handle(7) is h
    with pytest.raises(ValueError, match="already submitted"):
        cl.submit(SearchRequest(uid=7, seed=1, budget=2))
    h.result()
    with pytest.raises(ValueError, match="already submitted"):
        cl.submit(SearchRequest(uid=7, seed=1, budget=2))
    cl.close()


def test_cancel_before_admission():
    cl = _client(G=1)
    h0 = cl.submit(SearchRequest(uid=0, seed=0, budget=4))
    h1 = cl.submit(SearchRequest(uid=1, seed=1, budget=4))
    cl.poll(1)                            # uid=0 occupies the only slot
    assert h0.status() == "active" and h1.status() == "queued"
    assert h1.cancel() is True
    assert h1.status() == "cancelled" and h1.done()
    res = h1.result(wait=False)
    assert res.cancelled and not res.deadline_evicted
    assert res.actions == [] and res.supersteps == 0
    assert h1.cancel() is False           # already terminal
    assert h0.result().actions            # unaffected neighbour
    assert cl.stats.cancelled == 1
    cl.close()


def test_cancel_mid_flight_keeps_committed_moves():
    cl = _client(G=1)
    h = cl.submit(SearchRequest(uid=0, seed=2, budget=2, moves=4))
    cl.run_until(lambda c: len(c.core.move_log.get(0, [])) >= 2)
    assert h.status() == "active"
    assert h.cancel() is True
    res = h.result(wait=False)
    assert res.cancelled and len(res.actions) >= 2
    assert len(res.actions) < 4           # it really was cut short
    assert cl.core.pools[bucket_key(CFG_A)].load() == 0   # slot freed
    assert cl.poll(3) == 0                # nothing left to schedule
    cl.close()


def test_cancel_after_completion_is_noop():
    cl = _client()
    h = cl.submit(SearchRequest(uid=0, seed=3, budget=2))
    res = h.result()
    assert h.cancel() is False
    assert h.result(wait=False) is res and not res.cancelled
    cl.close()


def test_deadline_evicts_queued_request():
    cl = _client(G=1)
    cl.submit(SearchRequest(uid=0, seed=0, budget=8))
    h1 = cl.submit(SearchRequest(uid=1, seed=1, budget=2),
                   deadline_supersteps=3)
    cl.run_until(lambda c: h1.done())
    assert h1.status() == "evicted"
    res = h1.result(wait=False)
    assert res.cancelled and res.deadline_evicted and res.actions == []
    assert cl.stats.deadline_evictions == 1
    cl.close()


def test_deadline_evicts_in_flight_request_keeping_moves():
    cl = _client(G=1)
    h = cl.submit(SearchRequest(uid=0, seed=4, budget=2, moves=8),
                  deadline_supersteps=5)
    cl.run_until(lambda c: h.done())
    res = h.result(wait=False)
    assert h.status() == "evicted" and res.deadline_evicted
    assert 1 <= len(res.actions) < 8      # partial progress survived
    cl.close()


def test_generous_deadline_never_fires():
    cl = _client()
    h = cl.submit(SearchRequest(uid=0, seed=5, budget=2),
                  deadline_supersteps=10_000)
    res = h.result()
    assert h.status() == "done" and not res.cancelled
    assert cl.stats.deadline_evictions == 0
    cl.close()


# ---------------------------------------------------------------------------
# streamed moves()
# ---------------------------------------------------------------------------

def test_moves_stream_bit_identical_to_terminal_trace():
    """Acceptance: the per-move events streamed as reroots commit carry
    exactly the terminal result's action / reward / visit-distribution
    trace, in order, with `last` marking the final move."""
    cl = _client()
    h = cl.submit(SearchRequest(uid=0, seed=6, budget=3, moves=3))
    events = list(h.moves())              # iterating IS serving
    assert h.done()
    res = h.result(wait=False)
    assert [e.action for e in events] == res.actions
    assert [e.reward for e in events] == res.rewards
    assert [e.move_index for e in events] == list(range(len(res.actions)))
    for ev, vc in zip(events, res.visit_counts):
        assert isinstance(ev, MoveEvent)
        np.testing.assert_array_equal(ev.visit_counts, vc)
    assert [e.last for e in events] == [False, False, True]
    cl.close()


def test_moves_stream_interleaves_with_other_requests():
    """Events stream per handle even when several requests share the
    scheduler; a second pass over moves() replays from the buffer."""
    cl = _client()
    hs = [cl.submit(SearchRequest(uid=i, seed=10 + i, budget=2, moves=2))
          for i in range(3)]
    traces = {h.uid: [e.action for e in h.moves()] for h in hs}
    for h in hs:
        assert traces[h.uid] == h.result(wait=False).actions
        assert [e.action for e in h.moves()] == traces[h.uid]   # replay
    cl.close()


def test_moves_stream_ends_on_cancel():
    cl = _client(G=1)
    h = cl.submit(SearchRequest(uid=0, seed=2, budget=2, moves=6))
    it = h.moves()
    first = next(it)
    assert first.move_index == 0 and not first.last
    h.cancel()
    rest = list(it)                       # stream ends, no hang
    assert [e.move_index for e in rest] == \
        list(range(1, len(h.result(wait=False).actions)))
    cl.close()


# ---------------------------------------------------------------------------
# scheduling: priorities + policies + cross-pool fusion
# ---------------------------------------------------------------------------

def test_priority_reorders_admission():
    cl = _client(G=1)
    cl.submit(SearchRequest(uid=0, seed=0, budget=2))
    cl.poll(1)                                             # uid=0 occupies
    cl.submit(SearchRequest(uid=1, seed=1, budget=2))      # default class
    cl.submit(SearchRequest(uid=2, seed=2, budget=2), priority=5)
    done = [r.uid for r in cl.drain()]
    assert done == [0, 2, 1]              # priority 5 jumps the queue
    cl.close()


def _mix_requests():
    return [SearchRequest(uid=i, seed=20 + i, budget=3, moves=1 + i % 2,
                          cfg=cfg)
            for i, cfg in enumerate(MIX)]


def _dedicated_results():
    out = {}
    for req in _mix_requests():
        svc = ArenaPool(req.cfg, ENV, BanditValueBackend(), G=1, p=P)
        try:
            svc.submit(SearchRequest(uid=req.uid, seed=req.seed,
                                     budget=req.budget, moves=req.moves))
            (out[req.uid],) = svc.run()
        finally:
            svc.close()
    return out


def test_every_policy_matches_dedicated_services():
    """Policies move WHEN work happens, never WHAT it computes: the same
    heterogeneous mix under every policy equals dedicated single-config
    runs of each request, bit for bit."""
    want = _dedicated_results()
    for policy in POLICY_NAMES:
        cl = _client(G=2, policy=policy)
        try:
            handles = [cl.submit(req) for req in _mix_requests()]
            for h in handles:
                _assert_result_equal(h.result(), want[h.uid],
                                     f"{policy} uid={h.uid}")
        finally:
            cl.close()


def test_weighted_policy_fuses_across_pools():
    """The gang tick really fuses: one evaluate() spans >1 pool, and the
    fused batch is strictly larger than its largest single-pool share."""
    cl = _client(G=2, policy="weighted-queue-depth")
    for req in _mix_requests():
        cl.submit(req)
    cl.drain()
    core = cl.core
    assert core.xpool_batches > 0
    assert core.xpool_rows_max > core.xpool_pool_rows_max > 0
    # the aggregate view surfaces the fused batches too
    assert cl.stats.max_fused_rows == core.xpool_rows_max
    cl.close()


def test_fused_vs_per_pool_evaluate_bit_identical():
    """Acceptance: switching the gang tick between ONE cross-pool fused
    evaluate and per-pool evaluate changes nothing per request."""
    def go(fuse):
        cl = _client(G=2, policy="weighted-queue-depth",
                     fuse_across_pools=fuse)
        try:
            hs = [cl.submit(req) for req in _mix_requests()]
            return {h.uid: h.result() for h in hs}, cl.core.xpool_batches
        finally:
            cl.close()

    fused, nb_fused = go(True)
    split, nb_split = go(False)
    assert nb_fused > 0 and nb_split == 0
    for uid in fused:
        _assert_result_equal(fused[uid], split[uid], f"uid={uid}")


def test_weighted_policy_sizes_buckets_by_queue_depth():
    """Per-bucket G sizing: a bucket holding most of the backlog may fill
    its slots; a one-request bucket is capped to its fair share (>= 1)."""
    cl = _client(G=4, policy="weighted-queue-depth")
    for i in range(8):
        cl.submit(SearchRequest(uid=i, seed=i, budget=3, cfg=CFG_A))
    cl.submit(SearchRequest(uid=8, seed=8, budget=3, cfg=CFG_B))
    cl.poll(1)
    a = cl.core.pools[bucket_key(CFG_A)]
    b = cl.core.pools[bucket_key(CFG_B)]
    assert a.load() > b.load() >= 1       # depth-weighted, nobody starves
    assert b.admit_limit < a.admit_limit <= a.G
    assert len(cl.drain()) == 9           # sizing never loses a request
    cl.close()


def test_fairness_floor_prevents_admission_starvation():
    """Regression: a share-of-backlog cap of 1 is satisfied by a
    bucket's single long-running ACTIVE request, so its queued work used
    to starve behind a dominating bucket until that request finished.
    The fairness floor guarantees room for one fresh admission per gang
    tick; fairness_floor=False reproduces the starvation."""
    from repro.service.scheduler_core import WeightedQueueDepthPolicy

    def go(floor):
        cl = _client(G=4, policy=WeightedQueueDepthPolicy(
            fairness_floor=floor))
        # bucket B: one long-running request holds its only fair-share
        # slot, one small request queues behind it
        b1 = cl.submit(SearchRequest(uid=100, seed=1, budget=60, cfg=CFG_B))
        # bucket A dominates the depth share (cap_B stays at the floor)
        for i in range(12):
            cl.submit(SearchRequest(uid=i, seed=i, budget=20, cfg=CFG_A))
        b2 = cl.submit(SearchRequest(uid=101, seed=2, budget=2, cfg=CFG_B))
        cl.poll(6)
        b2_admitted = b2.status() != "queued"
        assert b1.status() == "active"    # B1 still occupies its slot
        assert len(cl.drain()) == 14      # floor or not, nothing is lost
        cl.close()
        return b2_admitted

    assert go(True)        # B2 admitted alongside B1 within a few ticks
    assert not go(False)   # starved behind B1 at the old share cap


def test_deadline_aware_policy_prefers_urgent_bucket():
    """The pool holding the nearest deadline advances first on every
    tick, so an urgent request on a cold bucket overtakes a deep default
    bucket."""
    cl = _client(G=1, policy="deadline-aware")
    for i in range(4):
        cl.submit(SearchRequest(uid=i, seed=i, budget=4, cfg=CFG_A))
    h = cl.submit(SearchRequest(uid=9, seed=9, budget=4, cfg=CFG_B),
                  deadline_supersteps=40)
    cl.drain()
    assert h.status() == "done"           # made its deadline
    by_finish = sorted(cl.core.completed, key=lambda r: r.done_at)
    assert by_finish[0].uid == 9          # urgent bucket went first
    cl.close()


# ---------------------------------------------------------------------------
# cold-pool retirement
# ---------------------------------------------------------------------------

def test_idle_pool_retires_and_resurrects_preserving_results():
    """Acceptance: an idle bucket releases its arena after the TTL
    (executor freed, session closed), keeps every completed result, and
    is resurrected on the next submit with bit-identical behavior."""
    cl = _client(G=2, retire_after_ticks=3)
    hb = cl.submit(SearchRequest(uid=0, seed=0, budget=2, cfg=CFG_B))
    cl.submit(SearchRequest(uid=1, seed=1, budget=40, cfg=CFG_A))
    key_b = bucket_key(CFG_B)
    cl.run_until(lambda c: c.core.pools[key_b].retired)
    pool_b = cl.core.pools[key_b]
    assert pool_b.exec is None and pool_b.sts is None
    assert cl.stats.retirements == 1
    assert hb.result(wait=False).actions            # result survived
    # resurrect on demand: same bucket, fresh arena, same computation
    hb2 = cl.submit(SearchRequest(uid=2, seed=0, budget=2, cfg=CFG_B))
    assert pool_b.retired is False and pool_b.exec is not None
    res2 = hb2.result()
    _assert_result_equal(res2, hb.result(wait=False), "resurrected run")
    assert cl.handle(0).status() == "done"          # old handle intact
    cl.close()


def test_busy_pool_never_retires():
    cl = _client(G=2, retire_after_ticks=1)
    h = cl.submit(SearchRequest(uid=0, seed=0, budget=6, moves=2))
    h.result()
    # the pool idles only after its work drained; no ticks follow, so it
    # stays live (retirement needs the scheduler to keep ticking)
    assert not cl.core.pools[bucket_key(CFG_A)].retired
    assert cl.stats.retirements == 0
    cl.close()


def test_result_ttl_expires_retired_pool_results():
    """Result TTL: a retired bucket's completed results are dropped after
    `result_ttl_ticks` global ticks — handle reports "expired" (done()
    stays True), result() raises, the move log is freed, and the expiry
    is counted in the registry.  Live buckets are untouched."""
    cl = _client(G=2, retire_after_ticks=2, result_ttl_ticks=4,
                 metrics=True)
    hb = cl.submit(SearchRequest(uid=0, seed=0, budget=2, cfg=CFG_B))
    h_long = cl.submit(SearchRequest(uid=1, seed=1, budget=60, cfg=CFG_A))
    cl.run_until(lambda c: c.handle(0).status() == "expired")
    assert hb.status() == "expired" and hb.done()
    with pytest.raises(RuntimeError, match="expired"):
        hb.result(wait=False)
    assert 0 not in cl.core.results and 0 not in cl.core.move_log
    assert cl.core.pools[bucket_key(CFG_B)].completed == []
    assert cl.registry.get("service_expired_results_total").value == 1
    # the still-live bucket keeps its result forever (pool never retired)
    assert h_long.result().actions and h_long.status() == "done"
    cl.close()


def test_no_ttl_keeps_retired_pool_results_forever():
    cl = _client(G=2, retire_after_ticks=2)        # result_ttl_ticks=None
    hb = cl.submit(SearchRequest(uid=0, seed=0, budget=2, cfg=CFG_B))
    cl.submit(SearchRequest(uid=1, seed=1, budget=40, cfg=CFG_A))
    cl.drain()
    assert cl.core.pools[bucket_key(CFG_B)].retired
    assert hb.status() == "done" and hb.result(wait=False).actions
    cl.close()


def test_moves_stream_survives_ttl_expiry_mid_iteration():
    """Regression: the result TTL pops move_log[uid] from the dict while
    a live moves() iterator may still be draining that list.  The
    iterator must hold the list object it first resolved — the expiry
    unlinks the dict entry but never mutates the list — so no event the
    iterator hasn't yielded yet is silently truncated."""
    cl = _client(G=2, retire_after_ticks=2, result_ttl_ticks=2)
    hb = cl.submit(SearchRequest(uid=0, seed=0, budget=2, moves=3,
                                 cfg=CFG_B))
    cl.submit(SearchRequest(uid=1, seed=1, budget=80, cfg=CFG_A))
    it = hb.moves()
    first = next(it)                     # iterator now holds the log list
    res = hb.result()                    # drive uid 0 to completion
    cl.run_until(lambda c: c.handle(0).status() == "expired")
    assert 0 not in cl.core.move_log     # the dict entry IS gone...
    got = [first] + list(it)             # ...but the stream is complete
    assert [e.action for e in got] == res.actions
    assert [e.move_index for e in got] == list(range(len(res.actions)))
    cl.close()


def test_retired_pool_probes_are_safe():
    """Regression: status() and the deadline-aware policy used to probe
    pool.slots directly, which a retired pool has released with its
    arena.  The retired-safe accessors (ArenaPool.holds /
    deadline_ticks) answer without touching freed state."""
    import math

    from repro.service.scheduler_core import DeadlineAwarePolicy

    cl = _client(G=2, retire_after_ticks=2)
    hb = cl.submit(SearchRequest(uid=0, seed=0, budget=2, cfg=CFG_B))
    cl.submit(SearchRequest(uid=1, seed=1, budget=40, cfg=CFG_A))
    key_b = bucket_key(CFG_B)
    cl.run_until(lambda c: c.core.pools[key_b].retired)
    pool = cl.core.pools[key_b]
    assert pool.exec is None             # arena really released
    assert pool.holds(0) is False
    assert pool.deadline_ticks() == []
    assert hb.status() == "done"         # handle probe on a retired pool
    assert DeadlineAwarePolicy()._slack(cl.core, key_b) == math.inf
    cl.close()


# ---------------------------------------------------------------------------
# EWMA-smoothed weighted-queue-depth admission caps
# ---------------------------------------------------------------------------

class _FakePool:
    def __init__(self, cfg, G, queued):
        self.cfg, self.G = cfg, G
        self.queue = [None] * queued

    def has_work(self):
        return True

    def load(self):
        return 0


class _FakeCore:
    def __init__(self, pools):
        self.pools = pools
        self._order = list(pools)
        self.ticks = 1
        from repro.obs import MetricsRegistry
        self.registry = MetricsRegistry()


def test_weighted_policy_smooths_admission_caps():
    """EWMA smoothing: when a bucket's burst drains in one tick, its cap
    decays over several ticks instead of collapsing straight to the
    floor, the EWMA is seeded with the first observed depth (tick 1
    behaves exactly as unsmoothed), the update advances at most once per
    tick, and the smoothed load is exported as a per-bucket gauge."""
    from repro.service.pool import bucket_label
    from repro.service.scheduler_core import WeightedQueueDepthPolicy

    pol = WeightedQueueDepthPolicy(ewma_alpha=0.5)
    a, b = _FakePool(CFG_A, 4, 8), _FakePool(CFG_B, 4, 8)
    core = _FakeCore({"a": a, "b": b})
    assert pol.admit_limits(core) == {"a": 2, "b": 2}   # seeded = unsmoothed
    b.queue = []          # the whole burst drains out of bucket b at once
    caps = []
    for tick in range(2, 6):
        core.ticks = tick
        caps.append(pol.admit_limits(core)["b"])
    # unsmoothed would slam to the floor (1) immediately; EWMA decays
    assert caps[0] > 1
    assert all(x >= y for x, y in zip(caps, caps[1:]))  # monotone decay
    assert caps[-1] >= 1
    # idempotent within a tick: probing again does not advance the EWMA
    assert pol.admit_limits(core)["b"] == caps[-1]
    gauge = core.registry.get("service_smoothed_load",
                              bucket=bucket_label(CFG_B))
    assert gauge is not None and 0 < gauge.value < 8
    # alpha=1 recovers the unsmoothed behavior; out-of-range rejected
    flat = WeightedQueueDepthPolicy(ewma_alpha=1.0)
    assert flat.admit_limits(core)["b"] == 1
    with pytest.raises(ValueError):
        WeightedQueueDepthPolicy(ewma_alpha=0.0)


def test_weighted_policy_prunes_drained_bucket_ewma():
    """Regression: _ewma entries for buckets that drained or retired
    were never pruned, so a bucket resurrected after idling reused the
    stale smoothed depth from its previous life and skewed every
    bucket's admission share.  No-work buckets are dropped each tick;
    a returning bucket reseeds from its fresh backlog."""
    from repro.service.scheduler_core import WeightedQueueDepthPolicy

    pol = WeightedQueueDepthPolicy(ewma_alpha=0.5)
    a, b = _FakePool(CFG_A, 4, 8), _FakePool(CFG_B, 4, 8)
    core = _FakeCore({"a": a, "b": b})
    pol.admit_limits(core)
    assert set(pol._ewma) == {"a", "b"}
    # bucket b drains (and, in the real core, retires): its entry goes
    b.queue = []
    b.has_work = lambda: False
    core.ticks = 2
    pol.admit_limits(core)
    assert set(pol._ewma) == {"a"}
    # resurrection: fresh backlog of 2 seeds the EWMA at 2 — NOT a decay
    # from the dead bucket's smoothed depth of 8
    b.queue = [None] * 2
    b.has_work = lambda: True
    core.ticks = 3
    pol.admit_limits(core)
    assert pol._ewma["b"] == 2


# ---------------------------------------------------------------------------
# D-sharded serving: least-loaded placement + failover
# ---------------------------------------------------------------------------

def test_shard_placement_balances_load():
    """Admissions go to the least-loaded enabled shard (ties break to
    the lowest shard id, then lowest free slot): four requests into a
    G=4 / D=2 pool alternate shards instead of filling shard 0 first."""
    cl = _client(G=4, n_shards=2)
    for i in range(4):
        cl.submit(SearchRequest(uid=i, seed=i, budget=30, moves=2))
    cl.poll(1)                            # first tick admits everything
    (pool,) = cl.core.pools.values()
    assert pool.n_shards == 2 and pool.shard_G == 2
    assert pool.shard_loads() == [2, 2]
    # uid 0 -> shard 0 (tie, lowest id), uid 1 -> shard 1 (now least
    # loaded), uid 2 -> shard 0 again, uid 3 -> shard 1
    assert [s.req.uid for s in pool.slots] == [0, 2, 1, 3]
    assert [pool.shard_of(g) for g in range(4)] == [0, 0, 1, 1]
    cl.close()


def test_shard_failover_disable_and_reenable():
    """set_shard_enabled steers admission around a drained shard: with
    shard 0 disabled every new request lands on shard 1; re-enabling
    restores least-loaded placement.  Results complete either way —
    placement never touches semantics."""
    cl = _client(G=4, n_shards=2)
    cl.submit(SearchRequest(uid=0, seed=0, budget=3))
    (pool,) = cl.core.pools.values()
    pool.set_shard_enabled(0, False)
    cl.submit(SearchRequest(uid=1, seed=1, budget=3))
    cl.poll(1)
    assert pool.shard_loads() == [0, 2]   # both on shard 1
    assert all(s is None for s in pool.slots[:2])
    pool.set_shard_enabled(0, True)
    cl.submit(SearchRequest(uid=2, seed=2, budget=3))
    cl.poll(1)
    assert pool.shard_loads()[0] == 1     # shard 0 takes work again
    done = {r.uid for r in cl.drain()}
    assert {0, 1, 2} <= done
    cl.close()


def test_shard_count_must_divide_g():
    with pytest.raises(ValueError, match="n_shards"):
        cl = _client(G=3, n_shards=2)
        cl.submit(SearchRequest(uid=0, seed=0, budget=2))


def test_resurrected_pool_keeps_shard_partition():
    """A retired sharded pool resurrects with the same D-way partition
    (the arena is rebuilt through the same factory arguments)."""
    cl = _client(G=4, n_shards=2, retire_after_ticks=2)
    cl.submit(SearchRequest(uid=0, seed=0, budget=2, cfg=CFG_B))
    cl.submit(SearchRequest(uid=1, seed=1, budget=40, cfg=CFG_A))
    key_b = bucket_key(CFG_B)
    cl.run_until(lambda c: c.core.pools[key_b].retired)
    h = cl.submit(SearchRequest(uid=2, seed=0, budget=2, cfg=CFG_B))
    pool = cl.core.pools[key_b]
    assert not pool.retired
    assert getattr(pool.exec, "n_shards", 1) == 2
    assert h.result().actions
    cl.close()


# ---------------------------------------------------------------------------
# tick budgets bound the CLOCK (fused dispatch advances it by up to K)
# ---------------------------------------------------------------------------

def test_run_max_ticks_bounds_clock_not_calls():
    """Regression: run(max_ticks) counted tick() CALLS, but one fused
    dispatch advances the clock by up to K — K=4 could burn 4x the
    stated budget.  The loop is now bounded against core.ticks and may
    overshoot by at most one dispatch."""
    cl = _client(G=2, supersteps_per_dispatch=4)
    cl.submit(SearchRequest(uid=0, seed=0, budget=60, moves=2))
    cl.core.run(max_ticks=8)
    assert cl.stats.fused_dispatches > 0  # the fused path really drove this
    assert cl.core.ticks < 8 + 4
    cl.close()


def test_result_max_ticks_bounds_clock_under_fused_dispatch():
    """Same bug on the handle: result(max_ticks) counted poll() calls.
    A request far from completion must stop within ~max_ticks of clock,
    not max_ticks dispatches."""
    cl = _client(G=2, supersteps_per_dispatch=8)
    h = cl.submit(SearchRequest(uid=0, seed=0, budget=200, moves=4))
    with pytest.raises(RuntimeError, match="no result"):
        h.result(max_ticks=16)
    assert cl.core.ticks < 16 + 8
    cl.close()


# ---------------------------------------------------------------------------
# overlap mode: budget exits drain the in-flight gang WITHOUT ticks
# ---------------------------------------------------------------------------

def _no_inflight(cl):
    return all(p._inflight is None and p._inflight_fused is None
               for p in cl.core.pools.values() if not p.retired)


def test_run_max_ticks_drains_inflight_gang_within_budget():
    """Regression (extends the PR 8 clock-bound fix to overlap): when
    run(max_ticks) expires mid-pipeline, the in-flight gang's applied
    selection/insertion must be completed — but by drain_overlap, which
    advances NO ticks, so the clock stays within the stated budget."""
    cl = _client(G=2, overlap=True)
    cl.submit(SearchRequest(uid=0, seed=0, budget=60, moves=2))
    cl.core.run(max_ticks=6)
    assert cl.core.ticks <= 6           # phase-path ticks are exactly 1
    assert _no_inflight(cl)             # ...and nothing was left applied
    assert cl.stats.supersteps > 0
    cl.close()


def test_result_max_ticks_drains_inflight_gang():
    """Same contract on the handle: result(max_ticks) exhausting its
    budget under overlap raises, stays within the clock bound, and
    leaves no gang in flight (its superstep completed tick-free)."""
    cl = _client(G=2, overlap=True)
    h = cl.submit(SearchRequest(uid=0, seed=0, budget=200, moves=4))
    with pytest.raises(RuntimeError, match="no result"):
        h.result(max_ticks=8)
    assert cl.core.ticks <= 8
    assert _no_inflight(cl)
    cl.close()


def test_run_until_budget_exit_drains_inflight_gang():
    """run_until's budget/drain exit calls drain_inflight before the
    final predicate check — the predicate observes a consistent pool."""
    cl = _client(G=2, overlap=True)
    cl.submit(SearchRequest(uid=0, seed=0, budget=100, moves=3))
    assert cl.run_until(lambda c: False, max_ticks=5) is False
    assert cl.core.ticks <= 5
    assert _no_inflight(cl)
    cl.close()


def test_run_max_ticks_bounds_clock_under_fused_overlap():
    """Overlap composes with the fused K-dispatch clock rule: one
    overlap tick collects the PREVIOUS gang's K-superstep dispatch, so
    the clock may overshoot by at most one dispatch — and the staged
    gang left in flight at budget expiry is drained tick-free."""
    cl = _client(G=2, overlap=True, supersteps_per_dispatch=4)
    cl.submit(SearchRequest(uid=0, seed=0, budget=60, moves=2))
    cl.core.run(max_ticks=8)
    assert cl.stats.fused_dispatches > 0
    assert cl.core.ticks < 8 + 4
    assert _no_inflight(cl)
    cl.close()


def test_overlap_results_match_lockstep_through_client():
    """The handle API returns bit-identical results with overlap on —
    gangs reschedule WHEN slots advance, never WHAT they compute."""
    reqs = [dict(uid=i, seed=i, budget=3 + i % 3, moves=1 + i % 2)
            for i in range(5)]

    def go(**kw):
        cl = _client(G=2, **kw)
        try:
            hs = [cl.submit(SearchRequest(**r)) for r in reqs]
            return {h.uid: h.result() for h in hs}
        finally:
            cl.close()

    want = go()
    got = go(overlap=True, n_gangs=2)
    for uid in want:
        _assert_result_equal(got[uid], want[uid], f"overlap uid={uid}")


def test_overlap_rejects_compaction():
    """Overlap pins slot rows while a gang is in flight — combining it
    with compaction (which moves rows) must fail loudly at build time."""
    with pytest.raises(ValueError, match="compact"):
        cl = _client(G=2, overlap=True, compact_threshold=0.5)
        cl.submit(SearchRequest(uid=0, seed=0, budget=2))
        cl.poll(1)
        cl.close()


# ---------------------------------------------------------------------------
# stats: monotonic ticks + wait histogram
# ---------------------------------------------------------------------------

def test_ticks_clock_and_wait_histogram():
    cl = _client(G=1)
    for i in range(3):
        cl.submit(SearchRequest(uid=i, seed=i, budget=2))
    cl.drain()
    s = cl.stats
    assert s.ticks == cl.core.ticks > 0   # the core's clock, not a sum
    assert sum(s.wait_supersteps.values()) == s.admitted == 3
    # G=1 serializes: the 2nd and 3rd request measurably waited
    assert max(s.wait_supersteps) > 0
    assert s.wait_percentile(0) <= s.wait_percentile(50) \
        <= s.wait_percentile(95) == max(s.wait_supersteps)
    cl.close()


def test_wait_histogram_merges_across_pools():
    from repro.service import ServiceStats
    a = ServiceStats(wait_supersteps={0: 2, 3: 1})
    b = ServiceStats(wait_supersteps={3: 2, 5: 1})
    m = a.merge(b)
    assert m.wait_supersteps == {0: 2, 3: 3, 5: 1}
    assert ServiceStats().wait_percentile(95) == 0


def test_pool_load_is_public_and_summaries_use_it():
    cl = _client(G=2)
    cl.submit(SearchRequest(uid=0, seed=0, budget=4))
    cl.poll(1)
    pool = cl.core.pools[bucket_key(CFG_A)]
    assert pool.load() == 1
    (summary,) = cl.pool_summaries()
    assert summary["active"] == 1 and summary["retired"] is False
    cl.drain()
    assert pool.load() == 0
    cl.close()


# ---------------------------------------------------------------------------
# package exports
# ---------------------------------------------------------------------------

def test_init_exports_new_names_first():
    """SearchClient heads the one exported surface; the compatibility
    names (adapters, executor aliases) and their modules are gone."""
    import importlib

    import repro.service as service
    exported = service.__all__
    assert exported.index("SearchClient") == 0
    assert set(exported) == {
        "SearchClient", "SearchHandle", "SchedulerCore", "SchedulePolicy",
        "POLICY_NAMES", "make_policy", "RoundRobinPolicy",
        "WeightedQueueDepthPolicy", "DeadlineAwarePolicy", "ArenaPool",
        "MoveEvent", "SearchRequest", "SearchResult", "ServiceStats"}
    assert not hasattr(service, "InTreeExecutor")   # lives in core.executor
    for mod in ("arena", "frontend", "scheduler"):
        with pytest.raises(ModuleNotFoundError):
            importlib.import_module(f"repro.service.{mod}")
