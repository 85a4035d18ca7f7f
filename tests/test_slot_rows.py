"""Row-bounded slot access: a slot read, re-root write and reset touch only
the rows covering the slot's tree, and leave the arena exactly as the
full-slot path did.

  * differential: after each row-bounded snapshot, re-root write and
    reset, the whole arena (every slot, every row, every leaf) equals the
    full-slot path's (read the whole slot, re-root all X rows, write or
    rebuild the whole slot) — on the reference, faithful, pallas and
    sharded executors, at tree sizes on the bucket edges and on trees
    grown by random supersteps;
  * invariant: through fused dispatches with commits and flushes, every
    row at or above a slot's size holds its initial value, and the
    full-width snapshots (keep_tree, slot_snapshot) equal the device slot;
  * counters: on a Pong-shaped pool each snapshot, write and reset
    touches the smallest bucket (ROW_FLOOR rows), not X, and the byte
    counters equal the rows that cross.
"""

import dataclasses

import jax
import numpy as np
import pytest

from repro.core import TreeConfig, make_intree_executor, ref_sequential
from repro.core.reroot import reroot
from repro.core.tree import (
    NULL, ROW_FLOOR, ROW_KEYS, UCTree, arena_set_slot, arena_slot,
    init_tree, pack_tree, row_bucket, to_jax, unpack_tree,
)
from repro.envs import BanditTreeEnv, BanditValueBackend
from repro.obs.metrics import MetricsRegistry
from repro.service import SearchRequest
from repro.service.pool import ArenaPool, bucket_label

CFG = TreeConfig(X=1024, F=3, D=8)
G, P = 2, 4
R = ROW_FLOOR
EDGE_SIZES = (1, R - 1, R, R + 1, CFG.X)


def _home(ex, g):
    """The executor holding slot g, and its row there."""
    return ex._locate(g) if hasattr(ex, "shards") else (ex, g)


def _whole(ex):
    """Every leaf of every slot, full width, read without the row-bounded
    path."""
    if hasattr(ex, "shards"):
        return [_whole(c) for c, _, _ in ex.shards]
    if isinstance(ex.trees, list):
        return [dataclasses.asdict(t.to_tree()) for t in ex.trees]
    return dataclasses.asdict(unpack_tree(ex.trees))


def _assert_same(a, b, label):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb), label
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=label)


# -- the full-slot path, as the executors did it before row bounding ----

def _old_snapshot(ex, g) -> dict:
    home, r = _home(ex, g)
    if isinstance(home.trees, list):
        return {k: np.asarray(v) for k, v in
                dataclasses.asdict(home.trees[r].to_tree()).items()}
    one = unpack_tree(arena_slot(home.trees, r))
    return {k: np.asarray(v) for k, v in dataclasses.asdict(one).items()}


def _old_set(ex, g, tree: UCTree):
    home, r = _home(ex, g)
    if isinstance(home.trees, list):
        home.trees[r] = ref_sequential.MutableTree.from_tree(tree)
    else:
        home.trees = arena_set_slot(home.trees, r, to_jax(pack_tree(tree)))


def _old_write(ex, g, full: dict):
    _old_set(ex, g, UCTree(**full))


def _old_reset(ex, g, na):
    xp = np if isinstance(_home(ex, g)[0].trees, list) else jax.numpy
    _old_set(ex, g, init_tree(CFG, na, xp=xp))


def _old_reroot(snap: dict, new_root: int) -> dict:
    """Re-root a full-width snapshot with a node queue, at all X rows."""
    X, child = CFG.X, snap["child"]
    order, seen = [int(new_root)], {int(new_root)}
    for n in order:
        for c in child[n]:
            if c != NULL and int(c) not in seen:
                seen.add(int(c))
                order.append(int(c))
    old2new = np.full(X, NULL, np.int32)
    old2new[order] = np.arange(len(order))
    n = len(order)
    out = {k: np.zeros_like(snap[k]) for k in ROW_KEYS}
    out["child"][:] = NULL
    for k in ROW_KEYS:
        out[k][:n] = snap[k][order]
    kept = child[order]
    out["child"][:n] = np.where(kept != NULL,
                                old2new[np.clip(kept, 0, X - 1)], NULL)
    out["node_depth"][:n] -= snap["node_depth"][new_root]
    out.update(size=np.int32(n), root=np.int32(0),
               log_table=snap["log_table"])
    return out, old2new


def _synthetic_tree(size: int, seed: int) -> dict:
    """A random tree of exactly `size` nodes, full width, rows at or above
    `size` initial."""
    rng = np.random.RandomState(seed)
    t = {k: np.asarray(v).copy() for k, v in
         dataclasses.asdict(init_tree(CFG, CFG.F, xp=np)).items()}
    lanes = np.zeros(CFG.X, np.int64)
    open_nodes = [0]
    for i in range(1, size):
        j = rng.randint(len(open_nodes))
        parent = open_nodes[j]
        t["child"][parent, lanes[parent]] = i
        lanes[parent] += 1
        if lanes[parent] == CFG.F:
            open_nodes.pop(j)
        t["node_depth"][i] = t["node_depth"][parent] + 1
        open_nodes.append(i)
    live = slice(0, size)
    t["num_actions"][live] = CFG.F
    t["num_expanded"][live] = lanes[live]
    for k in ("edge_N", "edge_W", "edge_VL", "edge_P"):
        t[k][live, : CFG.F] = rng.randint(0, 1 << 20, (size, CFG.F))
    for k in ("node_N", "node_O"):
        t[k][live] = rng.randint(0, 1 << 20, size)
    t["terminal"][live] = (lanes[live] == 0) & (rng.rand(size) < 0.3)
    t["size"] = np.int32(size)
    return t


def _drive(exs, rng, steps):
    """The same random supersteps on every executor in `exs`: insert each
    selected expansion, finalize it non-terminal, back up random values."""
    for _ in range(steps):
        active = rng.rand(G) < 0.8
        active[rng.randint(G)] = True
        values = rng.randint(-(1 << 16), 1 << 16, (G, P)).astype(np.int32)
        for ex in exs:
            sel_dev = ex.selection(active, P)
            sel = ex.sel_to_host(sel_dev)
            new_nodes = ex.insert(active, sel_dev)
            fin = np.full((G, P), NULL, np.int32)
            fin_na = np.zeros((G, P), np.int32)
            sim_nodes = np.zeros((G, P), np.int32)
            for g in np.flatnonzero(active):
                ins = new_nodes[g, :, 0]
                fin[g] = ins
                fin_na[g] = np.where(ins != NULL, CFG.F, 0)
                sim_nodes[g] = np.where(sel["expand_action"][g] >= 0, ins,
                                        sel["leaves"][g])
            ex.finalize(fin, fin_na, np.zeros((G, P), np.int32),
                        np.full((G, P), NULL, np.int32),
                        np.zeros((G, P, CFG.Fp), np.int32))
            ex.backup(active, sel_dev, sim_nodes, values, False)


def _make(kind):
    if kind == "sharded":
        return make_intree_executor(CFG, G, "faithful", n_shards=2)
    return make_intree_executor(CFG, G, kind)


def _commit(new, old, g, na, label):
    """Snapshot, re-root write and reset slot g: row-bounded on `new`,
    full-slot on `old`; the whole arenas must agree after each."""
    rows = new.slot_rows(g)
    full = _old_snapshot(old, g)
    size = int(full["size"])
    assert len(rows["child"]) == row_bucket(size, CFG.X), label
    assert int(rows["size"]) == size and int(rows["root"]) == int(
        full["root"]), label
    for k in ROW_KEYS:
        np.testing.assert_array_equal(rows[k], full[k][: len(rows[k])],
                                      err_msg=f"{label} {k}")
    padded = new.slot_snapshot(g)
    assert sorted(padded) == sorted(full), label
    for k in full:
        np.testing.assert_array_equal(padded[k], full[k],
                                      err_msg=f"{label} snapshot {k}")

    root = int(full["root"])
    kids = [int(c) for c in full["child"][root] if c != NULL]
    new_root = kids[-1] if kids else root
    arrays, old2new = reroot(CFG, rows, new_root)
    arrays_old, old2new_old = _old_reroot(full, new_root)
    np.testing.assert_array_equal(old2new, old2new_old[: len(old2new)])
    assert (old2new_old[len(old2new):] == NULL).all(), label
    new.write_slot(g, arrays)
    _old_write(old, g, arrays_old)
    _assert_same(_whole(new), _whole(old), f"{label} write")

    reset_size = int(arrays["size"])
    assert new.reset_slot(g, na) == row_bucket(reset_size, CFG.X), label
    _old_reset(old, g, na)
    _assert_same(_whole(new), _whole(old), f"{label} reset")


@pytest.mark.parametrize("case", [f"size{s}" for s in EDGE_SIZES]
                         + ["grown"])
@pytest.mark.parametrize("kind",
                         ["reference", "faithful", "pallas", "sharded"])
def test_row_bounded_ops_match_full_slot_path(kind, case):
    new, old = _make(kind), _make(kind)
    if case == "grown":
        # random supersteps and commits on both executors alike
        rng = np.random.RandomState(3)
        for ex in (new, old):
            for g in range(G):
                ex.reset_slot(g, CFG.F)
        for rnd in range(3):
            _drive((new, old), rng, steps=3)
            _assert_same(_whole(new), _whole(old), f"round {rnd}")
            _commit(new, old, rnd % G, 1 + rnd, f"{kind} round {rnd}")
        return
    size = int(case[4:])
    # slot 0 holds another tree, which no operation on slot 1 may touch
    for ex in (new, old):
        _old_write(ex, 0, _synthetic_tree(37, seed=1))
        _old_write(ex, 1, _synthetic_tree(size, seed=size))
    _assert_same(_whole(new), _whole(old), "setup")
    _commit(new, old, 1, 2, f"{kind} {case}")


# -- the invariant, through the served path -----------------------------

def _assert_rows_initial(pool):
    trees = unpack_tree(pool.exec.trees)
    fresh = init_tree(CFG, 0, xp=np)
    for g in range(G):
        size = int(trees.size[g])
        for k in ROW_KEYS:
            np.testing.assert_array_equal(
                getattr(trees, k)[g][size:], getattr(fresh, k)[size:],
                err_msg=f"slot {g} {k} above size {size}")


@pytest.mark.parametrize("executor,reuse", [
    ("faithful", True), ("faithful", False), ("pallas", True)],
    ids=["faithful-reroot", "faithful-flush", "pallas-reroot"])
def test_rows_above_size_stay_initial(executor, reuse):
    env = BanditTreeEnv(fanout=CFG.F, terminal_depth=12)
    pool = ArenaPool(CFG, env, BanditValueBackend(), G=G, p=P,
                     executor=executor, reuse_subtree=reuse,
                     supersteps_per_dispatch=4)
    for uid in range(5):
        pool.submit(SearchRequest(uid=uid, seed=uid, budget=4,
                                  moves=1 + uid % 3, keep_tree=True))
    kept = []

    def on_result(res):
        # called as the last move commits, while the request still holds
        # its slot and the slot still holds the finished tree
        g = next(g for g, s in enumerate(pool.slots)
                 if s is not None and s.req.uid == res.uid)
        want = unpack_tree(arena_slot(pool.exec.trees, g))
        for k, v in dataclasses.asdict(want).items():
            np.testing.assert_array_equal(res.tree_snapshot[k], v,
                                          err_msg=f"keep_tree {k}")
        kept.append(res.uid)

    pool.result_listener = on_result
    try:
        while pool.has_work():
            assert pool.fused_dispatch() > 0
            _assert_rows_initial(pool)
            for g in range(G):
                want = unpack_tree(arena_slot(pool.exec.trees, g))
                got = pool.exec.slot_snapshot(g)
                for k, v in dataclasses.asdict(want).items():
                    np.testing.assert_array_equal(got[k], v, err_msg=k)
    finally:
        pool.close()
    assert sorted(kept) == list(range(5))
    assert pool.stats.fused_dispatches > 0


# -- counters on a Pong-shaped pool --------------------------------------

def test_pong_shaped_slot_ops_touch_the_bucket():
    """Pong's fanout, depth, G, p, K and budget, with X cut to 4096 (its
    live trees stay under 256 rows): every op touches the smallest
    bucket, not X."""
    cfg = TreeConfig(X=4096, F=6, D=9)
    reg = MetricsRegistry()
    pool = ArenaPool(cfg, BanditTreeEnv(fanout=6, terminal_depth=12),
                     BanditValueBackend(), G=4, p=16, executor="faithful",
                     supersteps_per_dispatch=4, metrics=reg)
    for uid in range(6):
        pool.submit(SearchRequest(uid=uid, seed=1000 + uid, budget=8,
                                  moves=3 if uid % 2 else 1))
    try:
        pool.run()
    finally:
        pool.close()
    assert len(pool.completed) == 6
    label = bucket_label(cfg)
    W = 5 * cfg.Fp + 6          # int32 lanes per row in the packed block

    def value(name, **labels):
        m = reg.get(name, bucket=label, **labels)
        return 0 if m is None else m.value

    for op in ("snapshot", "write", "reset"):
        ops = value("service_slot_ops_total", op=op)
        assert ops > 0, op
        assert value("service_slot_rows_total", op=op) == R * ops, op
    for site, direction in (("snapshot", "d2h"), ("write", "h2d")):
        ops = value("service_slot_ops_total", op=site)
        rows = value("service_slot_rows_total", op=site)
        # each op moves its rows and the two scalars size and root
        assert value("service_host_transfer_bytes_total", site=site,
                     dir=direction) == 4 * (rows * W + 2 * ops), site
