"""Multi-arena frontend: config bucketing, routing bit-identity, and
persistent compaction sessions.

Three claim groups:

  * bucketing — core.tree.bucket_key groups configs iff nothing that can
    change a slot's bit evolution differs (fanout padding to Fp is the
    one semantics-free merge), and SearchClient routes each request to
    the pool of its bucket;
  * routing bit-identity (acceptance) — a heterogeneous request mix
    through the client produces, per request, results bit-identical to
    a dedicated single-config ArenaPool run of that request, for
    EVERY executor in EXECUTOR_NAMES;
  * sessions (acceptance) — with persistent compaction and a stable
    active set the sub-arena is gathered once and re-gathered only on
    membership changes (admission / eviction / reroot), snapshot reads
    force the deferred scatter, per-superstep and persistent modes are
    bit-identical, and the hysteresis thresholds stop decision thrash.
"""

import numpy as np
import pytest

from repro.core import TreeConfig
from repro.core.executor import EXECUTOR_NAMES
from repro.core.tree import bucket_key, canonical_config
from repro.envs import BanditTreeEnv, BanditValueBackend
from repro.service import ArenaPool, SearchClient, SearchRequest

ENV = BanditTreeEnv(fanout=3, terminal_depth=10)
P = 4

CFG_A = TreeConfig(X=128, F=4, D=6)
CFG_B = TreeConfig(X=96, F=3, D=5)      # different shape class
CFG_C = TreeConfig(X=128, F=3, D=6)     # same bucket as CFG_A (Fp=4)

MIX = [CFG_A, CFG_B, CFG_C, CFG_A, CFG_B]


# ---------------------------------------------------------------------------
# bucketing
# ---------------------------------------------------------------------------

def test_bucket_key_pads_fanout_only():
    assert bucket_key(CFG_A) == bucket_key(CFG_C)          # F=4 vs F=3, Fp=4
    assert bucket_key(CFG_A) != bucket_key(CFG_B)          # X and D differ
    base = TreeConfig(X=128, F=4, D=6)
    for other in (
        TreeConfig(X=64, F=4, D=6),                        # X is semantic
        TreeConfig(X=128, F=4, D=5),                       # D is semantic
        TreeConfig(X=128, F=8, D=6),                       # Fp differs
        TreeConfig(X=128, F=4, D=6, beta=2.0),
        TreeConfig(X=128, F=4, D=6, vl_mode="constant"),
        TreeConfig(X=128, F=4, D=6, score_fn="puct"),
        TreeConfig(X=128, F=4, D=6, leaf_mode="unexpanded",
                   expand_all=True),
    ):
        assert bucket_key(base) != bucket_key(other), other


def test_canonical_config_is_bucket_representative():
    canon = canonical_config(CFG_C)
    assert canon.F == CFG_C.Fp == 4
    assert bucket_key(canon) == bucket_key(CFG_C)
    assert canonical_config(canon) == canon


def test_frontend_routes_by_bucket():
    cl = SearchClient(ENV, BanditValueBackend(), G=2, p=P)
    pools = []
    for i, cfg in enumerate(MIX):
        req = SearchRequest(uid=i, seed=i, budget=2, cfg=cfg)
        cl.submit(req)
        pools.append(cl.core.pools[bucket_key(req.cfg)])
    assert len(cl.core.pools) == 2
    assert pools[0] is pools[2] is pools[3]                # CFG_A bucket
    assert pools[1] is pools[4]                            # CFG_B bucket
    assert pools[0] is not pools[1]
    cl.core.run()
    cl.close()


def test_frontend_requires_some_config():
    cl = SearchClient(ENV, BanditValueBackend(), G=2, p=P)
    with pytest.raises(ValueError, match="no TreeConfig"):
        cl.submit(SearchRequest(uid=0, seed=0))
    cl.close()


def test_default_cfg_serves_bare_requests():
    cl = SearchClient(ENV, BanditValueBackend(), G=2, p=P,
                      default_cfg=CFG_A)
    cl.submit(SearchRequest(uid=0, seed=0, budget=2))
    (res,) = cl.core.run()
    assert res.uid == 0 and res.actions
    cl.close()


def test_pool_rejects_foreign_config():
    pool = ArenaPool(CFG_A, ENV, BanditValueBackend(), G=2, p=P)
    with pytest.raises(ValueError, match="bucket"):
        pool.submit(SearchRequest(uid=0, seed=0, cfg=CFG_B))
    pool.close()


# ---------------------------------------------------------------------------
# routing bit-identity (acceptance)
# ---------------------------------------------------------------------------

def _mix_requests():
    return [SearchRequest(uid=i, seed=10 + i, budget=3, moves=1 + i % 2,
                          keep_tree=True, cfg=cfg)
            for i, cfg in enumerate(MIX)]


def _assert_result_equal(got, want, label):
    assert got.actions == want.actions, label
    assert got.rewards == want.rewards, label
    assert got.supersteps == want.supersteps, label
    for va, vb in zip(got.visit_counts, want.visit_counts):
        np.testing.assert_array_equal(va, vb, err_msg=label)
    for k in want.tree_snapshot:
        np.testing.assert_array_equal(
            got.tree_snapshot[k], want.tree_snapshot[k],
            err_msg=f"{label} field={k}")


@pytest.mark.parametrize("executor", EXECUTOR_NAMES)
def test_heterogeneous_mix_matches_dedicated_services(executor):
    """Acceptance: every request of a mixed-config batch through the
    client is bit-identical to the same request on a dedicated
    single-config ArenaPool of its own (unpadded) config."""
    cl = SearchClient(ENV, BanditValueBackend(), G=2, p=P,
                      executor=executor, compact_threshold=0.6,
                      persistent_compaction=True)
    try:
        for req in _mix_requests():
            cl.submit(req)
        done = {r.uid: r for r in cl.core.run()}
    finally:
        cl.close()
    assert sorted(done) == list(range(len(MIX)))

    for req in _mix_requests():
        svc = ArenaPool(req.cfg, ENV, BanditValueBackend(), G=1, p=P,
                        executor=executor)
        try:
            svc.submit(SearchRequest(uid=req.uid, seed=req.seed,
                                     budget=req.budget, moves=req.moves,
                                     keep_tree=True))
            (ref,) = svc.run()
        finally:
            svc.close()
        _assert_result_equal(done[req.uid], ref,
                             f"{executor} uid={req.uid}")


# ---------------------------------------------------------------------------
# persistent compaction sessions
# ---------------------------------------------------------------------------

def _low_occupancy_service(executor="faithful", persistent=True, **kw):
    # G=4 with a single active slot: always below the enter threshold,
    # so every superstep runs on the (gathered or resident) sub-arena
    return ArenaPool(CFG_A, ENV, BanditValueBackend(), G=4, p=P,
                     executor=executor, compact_threshold=0.5,
                     persistent_compaction=persistent, **kw)


@pytest.mark.parametrize("executor", ["reference", "faithful"])
def test_stable_set_gathers_once(executor):
    """Acceptance: a stable active set pays ONE gather for the whole run;
    the scatter is deferred to the eviction-time snapshot read."""
    budget = 6
    svc = _low_occupancy_service(executor)
    svc.submit(SearchRequest(uid=0, seed=1, budget=budget))
    svc.run()
    svc.close()
    s = svc.stats
    assert s.compacted_supersteps == budget
    assert s.session_gathers == 1
    assert s.session_reuses == budget - 1
    assert s.session_scatters == 1          # the final snapshot sync


def test_per_superstep_mode_regathers_every_superstep():
    """persistent_compaction=False restores the old cost model: one
    gather + one scatter per compacted superstep."""
    budget = 5
    svc = _low_occupancy_service(persistent=False)
    svc.submit(SearchRequest(uid=0, seed=1, budget=budget))
    svc.run()
    svc.close()
    s = svc.stats
    assert s.compacted_supersteps == budget
    assert s.session_gathers == budget
    assert s.session_reuses == 0


def test_admission_invalidates_session():
    """Admitting into a fresh slot changes the membership set, so exactly
    one extra gather happens — not one per superstep."""
    svc = _low_occupancy_service()
    svc.submit(SearchRequest(uid=0, seed=1, budget=7))
    for _ in range(3):
        svc.superstep()
    assert svc.stats.session_gathers == 1
    svc.submit(SearchRequest(uid=1, seed=2, budget=4))
    svc.run()
    svc.close()
    assert svc.stats.session_gathers == 2   # re-gather at the admission
    # ... plus the eviction of uid=1 (before uid=0 drains) re-gathers once
    # more at most; membership changes, never supersteps, drive gathers
    assert svc.stats.session_gathers + svc.stats.session_reuses \
        == svc.stats.compacted_supersteps


def test_reroot_invalidates_session_and_snapshot_forces_scatter():
    """A multi-move request reroots its slot in place at each move
    boundary: the membership set is unchanged but the slot's content is
    rewritten on the full arena, so the session must end (and the
    boundary's snapshot read must have scattered first)."""
    budget, moves = 4, 3
    svc = _low_occupancy_service()
    svc.submit(SearchRequest(uid=0, seed=3, budget=budget, moves=moves,
                             keep_tree=True))
    (res,) = svc.run()
    svc.close()
    s = svc.stats
    assert len(res.actions) == moves
    assert s.compacted_supersteps == budget * moves
    assert s.session_gathers == moves       # one per move segment
    assert s.session_reuses == (budget - 1) * moves
    assert s.session_scatters == moves      # each move's snapshot sync
    # the snapshot the result carries must include the last superstep's
    # work (the deferred scatter really happened before the read)
    snap = res.tree_snapshot
    assert np.all(snap["edge_VL"] == 0) and np.all(snap["node_O"] == 0)
    assert int(snap["size"]) > 1


@pytest.mark.parametrize("executor", ["reference", "faithful", "pallas"])
def test_persistent_sessions_bit_identical_to_per_superstep(executor):
    """Sessions are a pure cost optimization: deferring the scatter can
    never change what any slot computes."""
    def go(persistent):
        svc = ArenaPool(CFG_A, ENV, BanditValueBackend(), G=4, p=P,
                        executor=executor, compact_threshold=0.6,
                        persistent_compaction=persistent)
        try:
            for i in range(3):
                svc.submit(SearchRequest(uid=i, seed=30 + i,
                                         budget=3 + i, moves=1 + i % 2,
                                         keep_tree=True))
            return {r.uid: r for r in svc.run()}, svc.stats
        finally:
            svc.close()

    per, s_per = go(False)
    ses, s_ses = go(True)
    assert s_per.supersteps == s_ses.supersteps
    assert s_ses.session_gathers < s_per.session_gathers
    assert s_ses.session_reuses > 0
    for uid in per:
        _assert_result_equal(ses[uid], per[uid], f"uid={uid}")


def test_hysteresis_thresholds_stop_decision_thrash():
    """Occupancy oscillating between the enter and exit thresholds keeps
    the compacted decision stable; with exit == enter (default) the same
    oscillation flips the decision every tick."""
    def decisions(enter, exit_, As):
        svc = ArenaPool(CFG_A, ENV, BanditValueBackend(), G=8, p=P,
                        compact_threshold=enter,
                        compact_exit_threshold=exit_)
        out = []
        for a in As:
            active = np.zeros(8, bool)
            active[:a] = True
            svc._pick_execution(active)
            out.append(svc.last_decision["compacted"])
        svc.close()
        return out

    osc = [2, 3, 2, 3, 2]
    assert decisions(0.25, 0.5, osc) == [True] * 5          # hysteresis holds
    assert decisions(0.25, None, osc) == [True, False] * 2 + [True]
    # rising past the exit threshold really does exit, and the pool does
    # not re-enter until occupancy falls back below the enter threshold
    assert decisions(0.25, 0.5, [2, 4, 5, 4, 2]) == \
        [True, True, False, False, True]


def test_hysteresis_exit_below_enter_rejected():
    with pytest.raises(AssertionError, match="hysteresis"):
        ArenaPool(CFG_A, ENV, BanditValueBackend(), G=4, p=P,
                  compact_threshold=0.5, compact_exit_threshold=0.25)
