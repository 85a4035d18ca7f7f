"""Multi-tree search service: arena correctness + scheduler behaviour.

The load-bearing claims:
  1. the vmapped arena is a pure batching transform — every slot's tree
     evolves bit-identically to a single-tree run of the same request
     against the sequential numpy oracle;
  2. the scheduler actually schedules — more queued searches than slots
     complete, via admission into freed slots, with the Simulation phase
     fused across trees into one evaluate() batch.
"""

import numpy as np
import pytest

from repro.core import TreeConfig, TreeParallelMCTS
from repro.envs import BanditTreeEnv, BanditValueBackend
from repro.core.executor import (
    JaxExecutor, PallasExecutor, make_intree_executor,
)
from repro.service import ArenaPool, SearchRequest

CFG = TreeConfig(X=256, F=4, D=6)
ENV = BanditTreeEnv(fanout=4, terminal_depth=10)
P = 8


def _service(G, executor="faithful", **kw):
    return ArenaPool(CFG, ENV, BanditValueBackend(), G=G, p=P,
                     executor=executor, **kw)


def _single_tree_reference(seed, supersteps):
    m = TreeParallelMCTS(CFG, ENV, BanditValueBackend(), p=P,
                         executor="reference", seed=seed)
    for _ in range(supersteps):
        m.superstep()
    return m.exec.snapshot(m.tree), m.exec.best_action(m.tree)


def test_arena_bit_identical_to_single_tree_reference():
    """Acceptance (a): a G=4 arena run equals 4 independent single-tree
    runs of the sequential reference executor, bit for bit."""
    G, budget = 4, 6
    svc = _service(G)
    for i in range(G):
        svc.submit(SearchRequest(uid=i, seed=i, budget=budget, keep_tree=True))
    done = {r.uid: r for r in svc.run()}
    assert sorted(done) == list(range(G))
    for i in range(G):
        ref_snap, ref_action = _single_tree_reference(i, budget)
        snap = done[i].tree_snapshot
        for k in ref_snap:
            np.testing.assert_array_equal(ref_snap[k], snap[k],
                                          err_msg=f"uid={i} field={k}")
        assert done[i].actions == [ref_action]
        np.testing.assert_array_equal(
            done[i].visit_counts[0],
            ref_snap["edge_N"][int(ref_snap["root"])][: CFG.F])


def test_scheduler_oversubscription_and_fused_batching():
    """Acceptance (b): more queued searches than slots all complete
    (admission + eviction), and simulation batches span multiple trees."""
    G, n_req = 2, 5
    svc = _service(G)
    for i in range(n_req):
        svc.submit(SearchRequest(uid=i, seed=i, budget=4))
    done = svc.run()
    assert sorted(r.uid for r in done) == list(range(n_req))
    assert n_req > G
    # fused Simulation: while both slots were occupied, one evaluate()
    # call carried G * p rows (cross-tree batch), not p
    assert svc.stats.max_fused_rows == G * P
    assert svc.stats.sim_batches == svc.stats.supersteps
    # 5 searches x 4 supersteps over 2 slots => at least ceil(20/2) ticks
    assert svc.stats.supersteps >= 10


def test_reference_arena_matches_jit_arena():
    """The scheduler is executor-agnostic: the sequential per-slot oracle
    and the vmapped jit arena produce identical results and schedules."""
    def go(executor):
        svc = _service(2, executor=executor)
        for i in range(4):
            svc.submit(SearchRequest(uid=i, seed=10 + i, budget=5,
                                     keep_tree=True))
        return {r.uid: r for r in svc.run()}

    a, b = go("reference"), go("faithful")
    assert sorted(a) == sorted(b)
    for uid in a:
        assert a[uid].actions == b[uid].actions
        assert a[uid].supersteps == b[uid].supersteps
        for k in a[uid].tree_snapshot:
            np.testing.assert_array_equal(
                a[uid].tree_snapshot[k], b[uid].tree_snapshot[k],
                err_msg=f"uid={uid} field={k}")


def test_multi_move_request_advances_via_reroot():
    """A long-lived request plays several moves on one slot; the chosen
    subtree's statistics survive each move boundary and the quiescence
    invariants (VL == O == 0) hold at eviction."""
    svc = _service(2)
    svc.submit(SearchRequest(uid=0, seed=3, budget=5, moves=3,
                             keep_tree=True))
    (res,) = svc.run()
    assert len(res.actions) == len(res.rewards) == len(res.visit_counts) == 3
    snap = res.tree_snapshot
    assert np.all(snap["edge_VL"] == 0) and np.all(snap["node_O"] == 0)
    # subtree reuse means later moves start warm: the tree at eviction is
    # bigger than one move's insertions alone would leave after a flush
    assert int(snap["size"]) > 1
    assert res.supersteps == 15


def test_multi_move_flush_fallback_matches_fresh_searches():
    """With subtree reuse off, every move starts from a flushed tree — so move
    k of a multi-move request equals a fresh single-move search from the
    same state."""
    svc = _service(1, reuse_subtree=False)
    svc.submit(SearchRequest(uid=0, seed=7, budget=4, moves=2))
    (res,) = svc.run()

    # replay move 2 as its own request from the post-move-1 state
    s1, _, _ = ENV.step(ENV.initial_state(7), res.actions[0])

    class _Env(BanditTreeEnv):
        def initial_state(self, seed):
            return s1

    svc2 = ArenaPool(CFG, _Env(fanout=4, terminal_depth=10),
                     BanditValueBackend(), G=1, p=P, executor="faithful")
    svc2.submit(SearchRequest(uid=1, seed=0, budget=4))
    (res2,) = svc2.run()
    assert res.actions[1] == res2.actions[0]
    np.testing.assert_array_equal(res.visit_counts[1], res2.visit_counts[0])


def test_idle_slots_are_frozen():
    """An occupied slot's tree must be untouched by supersteps that only
    concern other slots: admit one request on a G=3 arena and check the
    other slots stay at their initial state."""
    svc = _service(3)
    svc.submit(SearchRequest(uid=0, seed=1, budget=3))
    svc.run()
    for g in (1, 2):
        snap = svc.exec.slot_snapshot(g)
        assert int(snap["size"]) == 1
        assert snap["node_N"].sum() == 0 and snap["edge_N"].sum() == 0


def test_staggered_admission_is_deterministic():
    """Requests admitted mid-flight (into a freed slot) see exactly the
    same search as when run alone: scheduling changes when a tree's
    supersteps happen, never what they compute."""
    svc = _service(2)
    for i in range(6):
        svc.submit(SearchRequest(uid=i, seed=20 + i, budget=3,
                                 keep_tree=True))
    done = {r.uid: r for r in svc.run()}
    # uid=5 was admitted after several evictions; compare to a solo run
    solo = _service(1)
    solo.submit(SearchRequest(uid=5, seed=25, budget=3, keep_tree=True))
    (ref,) = solo.run()
    assert done[5].actions == ref.actions
    for k in ref.tree_snapshot:
        np.testing.assert_array_equal(ref.tree_snapshot[k],
                                      done[5].tree_snapshot[k], err_msg=k)


def test_expand_all_puct_service_runs():
    """Gomoku-style config (expand-all + PUCT priors) through the fused
    service path: priors are split per slot and the trees stay quiescent."""
    import jax
    from repro.envs import GomokuEnv
    from repro.envs.policy_net import NNSimBackend, init_params

    env = GomokuEnv()
    cfg = TreeConfig(X=128, F=36, D=5, beta=5.0, score_fn="puct",
                     leaf_mode="unexpanded", expand_all=True)
    backend = NNSimBackend(env, init_params(jax.random.PRNGKey(0)))
    svc = ArenaPool(cfg, env, backend, G=2, p=4, executor="faithful",
                    alternating_signs=True)
    for i in range(2):
        svc.submit(SearchRequest(uid=i, seed=i, budget=3, keep_tree=True))
    done = svc.run()
    assert len(done) == 2
    for r in done:
        s = r.tree_snapshot
        assert int(s["size"]) > 1
        assert np.all(s["edge_VL"] == 0) and np.all(s["node_O"] == 0)
        assert s["edge_P"].any()  # priors landed


def test_pallas_is_first_class_arena_executor():
    """The arena-native kernels serve the arena directly: "pallas" is its
    own executor in the unified stack (not a JaxExecutor variant, which
    still rejects the name — the jit and kernel paths stay distinct)."""
    ex = make_intree_executor(CFG, 2, "pallas")
    assert isinstance(ex, PallasExecutor)
    assert ex.G == 2
    with pytest.raises(NotImplementedError):
        JaxExecutor(CFG, 2, variant="pallas")
