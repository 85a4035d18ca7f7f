"""Arena-native Pallas path: bit-identical slot evolution vs the vmapped
jit arena and the sequential numpy oracle, across random occupancy masks
and the compacted low-occupancy execution path.

Three layers of evidence:
  1. raw executor protocol driven with random [G] active masks — every
     phase (selection / insert / finalize / backup) produces the same
     per-slot trees on reference / faithful / pallas, and inactive slots
     stay bit-frozen;
  2. ArenaPool(executor="pallas") end to end equals G independent
     single-tree runs of the numpy oracle (the acceptance claim);
  3. masked vs compacted execution are interchangeable: the same workload
     with compaction disabled and enabled returns identical results while
     the compacted run actually exercises gather_sub/scatter_sub.
"""

import numpy as np
import pytest

from repro.core import TreeConfig, TreeParallelMCTS, make_intree_executor
from repro.core.tree import NULL
from repro.envs import BanditTreeEnv, BanditValueBackend
from repro.service import ArenaPool, SearchRequest

CFG = TreeConfig(X=256, F=4, D=6)
CFG_ALL = TreeConfig(X=256, F=4, D=5, score_fn="puct",
                     leaf_mode="unexpanded", expand_all=True)
ENV = BanditTreeEnv(fanout=4, terminal_depth=10)
P = 6
G = 4

EXECUTORS = ("reference", "faithful", "pallas")


def _random_masks(steps, seed):
    rng = np.random.RandomState(seed)
    masks = []
    for _ in range(steps):
        m = rng.rand(G) < 0.6
        if not m.any():
            m[rng.randint(G)] = True
        masks.append(m)
    return masks


def _drive_raw(cfg, name, masks, values):
    """Drive the executor protocol without an env: insert every selected
    expansion, finalize it non-terminal with F actions, back up canned
    values.  Pure array flow — identical inputs for every executor."""
    ex = make_intree_executor(cfg, G, name)
    for g in range(G):
        ex.reset_slot(g, cfg.F)
    K = P * cfg.Fp if cfg.expand_all else P
    for step, active in enumerate(masks):
        sel_dev = ex.selection(active, P)
        sel = ex.sel_to_host(sel_dev)
        new_nodes = ex.insert(active, sel_dev)              # [G, P, Fp]
        fin_nodes = np.full((G, K), NULL, np.int32)
        fin_na = np.zeros((G, K), np.int32)
        fin_term = np.zeros((G, K), np.int32)
        sim_nodes = np.zeros((G, P), np.int32)
        for g in np.flatnonzero(active):
            ins = new_nodes[g].reshape(-1)
            ins = ins[ins != NULL][:K]
            fin_nodes[g, : len(ins)] = ins
            fin_na[g, : len(ins)] = cfg.F
            single = sel["expand_action"][g] >= 0
            sim_nodes[g] = np.where(single, new_nodes[g, :, 0],
                                    sel["leaves"][g])
        ex.finalize(fin_nodes, fin_na, fin_term,
                    np.full((G, P), NULL, np.int32),
                    np.zeros((G, P, cfg.Fp), np.int32))
        ex.backup(active, sel_dev, sim_nodes, values[step], False)
    return [ex.slot_snapshot(g) for g in range(G)]


@pytest.mark.parametrize("cfg", [CFG, CFG_ALL],
                         ids=["single-expand", "expand-all-puct"])
def test_executors_agree_under_random_masks(cfg):
    steps = 5
    masks = _random_masks(steps, seed=11)
    rng = np.random.RandomState(7)
    from repro.core import fixedpoint as fx
    values = np.asarray(
        fx.encode(rng.uniform(-1, 1, (steps, G, P)).astype(np.float32)),
        np.int32)
    snaps = {name: _drive_raw(cfg, name, masks, values)
             for name in EXECUTORS}
    for name in ("faithful", "pallas"):
        for g in range(G):
            for k in snaps["reference"][g]:
                np.testing.assert_array_equal(
                    snaps["reference"][g][k], snaps[name][g][k],
                    err_msg=f"{name} slot={g} field={k}")


def test_inactive_slots_bit_frozen_on_pallas():
    """A slot that is never activated must be untouched by the kernels."""
    masks = [np.array([True, False, True, False])] * 3
    rng = np.random.RandomState(3)
    from repro.core import fixedpoint as fx
    values = np.asarray(
        fx.encode(rng.uniform(-1, 1, (3, G, P)).astype(np.float32)),
        np.int32)
    snaps = _drive_raw(CFG, "pallas", masks, values)
    for g in (1, 3):
        assert int(snaps[g]["size"]) == 1
        assert snaps[g]["node_N"].sum() == 0
        assert snaps[g]["edge_N"].sum() == 0
        assert snaps[g]["edge_VL"].sum() == 0
        assert snaps[g]["node_O"].sum() == 0


def _single_tree_reference(seed, supersteps):
    m = TreeParallelMCTS(CFG, ENV, BanditValueBackend(), p=P,
                         executor="reference", seed=seed)
    for _ in range(supersteps):
        m.superstep()
    return m.exec.snapshot(m.tree), m.exec.best_action(m.tree)


def test_pallas_service_bit_identical_to_numpy_oracle():
    """Acceptance: ArenaPool(executor='pallas') end to end — every
    slot's tree evolution equals an independent single-tree run of the
    sequential numpy oracle, bit for bit."""
    budget = 5
    svc = ArenaPool(CFG, ENV, BanditValueBackend(), G=G, p=P,
                    executor="pallas")
    for i in range(G):
        svc.submit(SearchRequest(uid=i, seed=i, budget=budget,
                                 keep_tree=True))
    done = {r.uid: r for r in svc.run()}
    assert sorted(done) == list(range(G))
    for i in range(G):
        ref_snap, ref_action = _single_tree_reference(i, budget)
        snap = done[i].tree_snapshot
        for k in ref_snap:
            np.testing.assert_array_equal(ref_snap[k], snap[k],
                                          err_msg=f"uid={i} field={k}")
        assert done[i].actions == [ref_action]


@pytest.mark.parametrize("executor", ["faithful", "pallas"])
def test_compacted_equals_masked(executor):
    """Mixed budgets drain the arena unevenly, so occupancy decays and the
    threshold run compacts while the disabled run masks — results must be
    bit-identical and the compacted path must actually trigger."""
    def go(thresh):
        svc = ArenaPool(CFG, ENV, BanditValueBackend(), G=G, p=P,
                        executor=executor, compact_threshold=thresh)
        for i in range(3):
            svc.submit(SearchRequest(uid=i, seed=40 + i, budget=3 + 2 * i,
                                     keep_tree=True))
        return {r.uid: r for r in svc.run()}, svc.stats

    masked, s_masked = go(0.0)
    compacted, s_comp = go(0.5)
    assert s_masked.compacted_supersteps == 0
    assert s_comp.compacted_supersteps > 0
    assert sorted(masked) == sorted(compacted)
    for uid in masked:
        assert masked[uid].actions == compacted[uid].actions
        assert masked[uid].supersteps == compacted[uid].supersteps
        for k in masked[uid].tree_snapshot:
            np.testing.assert_array_equal(
                masked[uid].tree_snapshot[k],
                compacted[uid].tree_snapshot[k],
                err_msg=f"uid={uid} field={k}")
