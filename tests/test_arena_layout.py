"""The arena's device layout: every leaf is stored in the packed rows the
Pallas kernels read (core/tree.PackedTree), and no device program converts
it to the logical [G, X, Fp] / [G, X] layout or back.

  * host view: pack_tree / unpack_tree and pack_rows / unpack_rows round-
    trip, at an X that is not a multiple of 128/Fp or of 128, and the
    padding holds initial values;
  * accessors: the packed-row reads and writes agree with logical
    indexing, and a write outside [0, X) x [0, Fp) is dropped (it never
    lands in the padding or on a neighbour's edge block);
  * row programs: at every bucket from 512 to X, the slot read, write and
    reset touch exactly the logical rows [0, R) of one slot;
  * HLO guard: the optimized programs (fused loop on the pallas and jnp
    variants, the K=1 phase programs, the row programs) hold no array of
    an unpacked arena shape, so no reshape, transpose or copy into that
    layout can hide in them.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import TreeConfig, intree
from repro.core.fused import _fused_program
from repro.core.tree import (
    EDGE_KEYS, LANES, NODE_KEYS, NULL, ROW_KEYS, SUBLANES, UCTree,
    edge_add, edge_get, edge_row, edge_set, init_arena, init_rows, init_tree,
    make_log_table, node_add, node_get, node_set, pack_rows, pack_tree,
    read_slot_rows, reset_slot_rows, row_bucket, stored_rows, to_jax,
    unpack_rows, unpack_tree, write_slot_rows,
)
from repro.envs import BanditTreeEnv, BanditValueBackend
from repro.kernels import ops as kops

# X=100 is a multiple of neither 128/Fp nor 128: both paddings exist
SMALL = {8: TreeConfig(X=100, F=6, D=4), 64: TreeConfig(X=100, F=36, D=3)}


def _random_tree(cfg, G, seed):
    """G logical trees of random statistics (numpy)."""
    rng = np.random.RandomState(seed)
    t = {k: np.array(v) for k, v in dataclasses.asdict(
        init_tree(cfg, xp=np)).items()}
    out = {}
    for k, v in t.items():
        if k in ROW_KEYS:
            out[k] = rng.randint(-1000, 1000, (G,) + v.shape).astype(np.int32)
        else:
            out[k] = np.stack([v] * G)
    return UCTree(**out)


# -- host view ----------------------------------------------------------

@pytest.mark.parametrize("Fp", sorted(SMALL))
def test_pack_tree_roundtrip(Fp):
    cfg = SMALL[Fp]
    assert cfg.Fp == Fp
    logical = _random_tree(cfg, 3, seed=Fp)
    packed = pack_tree(logical)
    er, nr, lr = stored_rows(cfg.X, Fp)
    assert (packed.X, packed.Fp) == (cfg.X, Fp)
    for k in EDGE_KEYS:
        assert getattr(packed, k).shape == (3, er, LANES), k
    for k in NODE_KEYS:
        assert getattr(packed, k).shape == (3, nr, LANES), k
    assert packed.log_table.shape == (3, lr, LANES)
    assert er % SUBLANES == nr % SUBLANES == lr % SUBLANES == 0
    assert er * LANES >= cfg.X * Fp and nr * LANES >= cfg.X
    back = unpack_tree(packed)
    for k, v in dataclasses.asdict(logical).items():
        np.testing.assert_array_equal(getattr(back, k), v, err_msg=k)
    # padding past node X holds initial values
    flat = packed.child.reshape(3, -1)
    assert (flat[:, cfg.X * Fp:] == NULL).all()
    assert (packed.node_N.reshape(3, -1)[:, cfg.X:] == 0).all()


@pytest.mark.parametrize("Fp", sorted(SMALL))
def test_init_arena_is_the_packed_fresh_tree(Fp):
    cfg = SMALL[Fp]
    got = jax.device_get(init_arena(cfg, 2, root_num_actions=5))
    want = pack_tree(UCTree(**{
        k: np.stack([np.asarray(v)] * 2) for k, v in dataclasses.asdict(
            init_tree(cfg, 5, xp=np)).items()}))
    for k, v in dataclasses.asdict(want).items():
        np.testing.assert_array_equal(getattr(got, k), v, err_msg=k)


@pytest.mark.parametrize("Fp", sorted(SMALL))
def test_pack_rows_roundtrip(Fp):
    cfg = TreeConfig(X=1000, F=SMALL[Fp].F, D=3)
    rng = np.random.RandomState(Fp)
    for R in (512, cfg.X):
        rows = {k: rng.randint(0, 99, v.shape).astype(np.int32) for k, v in
                init_rows(Fp, R, 1, np).items() if k in ROW_KEYS}
        rows.update(size=np.int32(7), root=np.int32(3))
        back = unpack_rows(cfg, pack_rows(cfg, rows, R), R)
        for k, v in rows.items():
            np.testing.assert_array_equal(back[k], v, err_msg=f"{R} {k}")


# -- accessors ----------------------------------------------------------

@pytest.mark.parametrize("Fp", sorted(SMALL))
def test_accessors_match_logical_indexing(Fp):
    cfg = SMALL[Fp]
    logical = _random_tree(cfg, 1, seed=3)
    t = jax.tree.map(lambda a: jnp.asarray(a[0]), pack_tree(logical))
    lg = jax.tree.map(lambda a: a[0], logical)
    rng = np.random.RandomState(Fp)
    nodes = rng.randint(0, cfg.X, 17).astype(np.int32)
    acts = rng.randint(0, Fp, 17).astype(np.int32)
    np.testing.assert_array_equal(edge_row(t, t.edge_W, nodes),
                                  lg.edge_W[nodes])
    np.testing.assert_array_equal(edge_get(t, t.child, nodes, acts),
                                  lg.child[nodes, acts])
    np.testing.assert_array_equal(node_get(t.node_O, nodes), lg.node_O[nodes])
    np.testing.assert_array_equal(node_get(t.log_table, nodes),
                                  make_log_table(cfg.X)[nodes])

    # writes, with out-of-range ids mixed in: NULL, X, the action Fp
    bad_n = np.array([NULL, cfg.X, cfg.X + 5, 0], np.int32)
    bad_a = np.array([0, 0, 0, Fp], np.int32)
    n_all = np.concatenate([np.unique(nodes), bad_n])
    a_all = np.concatenate([np.zeros(len(np.unique(nodes)), np.int32),
                            bad_a])
    val = np.arange(len(n_all), dtype=np.int32) + 10000
    keep = np.arange(len(n_all)) < len(np.unique(nodes))

    def check(got_packed, want_logical, key):
        got = unpack_tree(dataclasses.replace(t, **{key: got_packed}))
        np.testing.assert_array_equal(getattr(got, key), want_logical,
                                      err_msg=key)
        pad = np.asarray(got_packed).reshape(-1)
        n_live = cfg.X * (Fp if key in EDGE_KEYS else 1)
        fresh = NULL if key == "child" else 0
        assert (pad[n_live:] == fresh).all(), f"{key} padding written"

    want = lg.child.copy()
    want[n_all[keep], a_all[keep]] = val[keep]
    check(edge_set(t, t.child, n_all, a_all, val), want, "child")
    want = lg.edge_N.copy()
    np.add.at(want, (n_all[keep], a_all[keep]), val[keep])
    check(edge_add(t, t.edge_N, n_all, a_all, val), want, "edge_N")
    # node 0 is out of range only as an edge (action Fp): drop that pair
    n_all, val, keep = n_all[:-1], val[:-1], keep[:-1]
    want = lg.node_depth.copy()
    want[n_all[keep]] = val[keep]
    check(node_set(t, t.node_depth, n_all, val), want, "node_depth")
    want = lg.node_N.copy()
    np.add.at(want, n_all[keep], val[keep])
    check(node_add(t, t.node_N, n_all, val), want, "node_N")


# -- row programs at every bucket ----------------------------------------

ROW_CFGS = [TreeConfig(X=1000, F=6, D=4), TreeConfig(X=1000, F=36, D=3)]


@pytest.mark.parametrize("cfg", ROW_CFGS, ids=lambda c: f"Fp{c.Fp}")
def test_row_programs_touch_exactly_the_bucket(cfg):
    G, g = 2, 1
    buckets = sorted({row_bucket(n, cfg.X) for n in (1, 513, cfg.X)})
    assert buckets == [512, cfg.X]
    rng = np.random.RandomState(cfg.Fp)
    base = _random_tree(cfg, G, seed=cfg.Fp)
    arena = to_jax(pack_tree(base))
    for R in buckets:
        # read
        rows = unpack_rows(cfg, jax.device_get(
            read_slot_rows(arena, np.int32(g), R)), R)
        for k in ROW_KEYS:
            np.testing.assert_array_equal(rows[k], getattr(base, k)[g][:R],
                                          err_msg=f"read {R} {k}")
        assert rows["size"] == base.size[g] and rows["root"] == base.root[g]

        # write: rows [0, R) of slot g and its size and root, nothing else
        new = {k: rng.randint(0, 99, getattr(base, k)[g][:R].shape)
               .astype(np.int32) for k in ROW_KEYS}
        new.update(size=np.int32(R // 2), root=np.int32(1))
        got = unpack_tree(write_slot_rows(arena, np.int32(g),
                                          pack_rows(cfg, new, R)))
        for k in ROW_KEYS:
            want = getattr(base, k).copy()
            want[g][:R] = new[k]
            np.testing.assert_array_equal(getattr(got, k), want,
                                          err_msg=f"write {R} {k}")
        np.testing.assert_array_equal(got.size, [base.size[0], R // 2])
        np.testing.assert_array_equal(got.root, [base.root[0], 1])

        # reset: rows [0, R) fresh, with 4 root actions
        got = unpack_tree(reset_slot_rows(arena, np.int32(g), np.int32(4),
                                          R))
        fresh = init_rows(cfg.Fp, R, 4, np)
        for k in ROW_KEYS:
            want = getattr(base, k).copy()
            want[g][:R] = fresh[k]
            np.testing.assert_array_equal(getattr(got, k), want,
                                          err_msg=f"reset {R} {k}")
        np.testing.assert_array_equal(got.size, [base.size[0], 1])
        np.testing.assert_array_equal(got.root, [base.root[0], 0])


# -- HLO guard -----------------------------------------------------------

G, P, K = 3, 5, 3


def _unpacked_shapes(cfg):
    """Regexes of every unpacked arena shape, with and without the slot
    axis: logical edges [X, Fp], edges padded to whole rows or to the
    stored tiles, logical and padded node arrays, the logical log table."""
    X, Fp = cfg.X, cfg.Fp
    er, nr, _ = stored_rows(X, Fp)
    xe = {X, -(-X * Fp // LANES) * LANES // Fp, er * LANES // Fp}
    xn = {X, -(-X // LANES) * LANES, nr * LANES}
    shapes = {f"s32[{x},{Fp}]" for x in xe} | {f"s32[{x}]" for x in xn}
    shapes.add(f"f32[{2 * X + 4}]")
    shapes |= {s.replace("[", f"[{G},") for s in shapes}
    return [re.escape(s) for s in shapes]


def _arena_args(cfg):
    env = BanditTreeEnv(fanout=cfg.F, terminal_depth=cfg.D + 1)
    arena = init_arena(cfg, G)
    active = jnp.ones(G, bool)
    sel = jax.eval_shape(lambda a: intree.select_arena(cfg, a, active, P),
                         arena)[1]
    return env, arena, active, sel


def _programs(cfg):
    """(name, lowered) of every device program that touches the arena."""
    env, arena, active, sel = _arena_args(cfg)
    sim = BanditValueBackend()
    states = jnp.zeros((G, cfg.X) + env.state_shape, jnp.float32)
    budget = jnp.full(G, 4, jnp.int32)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    fin = (i32(G, P), i32(G, P), i32(G, P), i32(G, P), i32(G, P, cfg.Fp))
    blocks = jax.eval_shape(read_slot_rows, arena, np.int32(0), 512)
    out = [(f"fused-{v}", _fused_program.lower(
        cfg, v, P, K, env, sim, False, arena, states, active, budget))
        for v in ("pallas", "faithful")]
    out += [(f"select-{v}",
             intree.select_arena.lower(cfg, arena, active, P, v))
            for v in ("faithful", "relaxed", "wavefront")]
    out += [
        ("select-pallas", kops.select_arena.lower(cfg, arena, active, P)),
        ("insert", intree.insert_arena.lower(cfg, arena, active, sel)),
        ("finalize", intree.finalize_arena.lower(arena, *fin)),
        ("backup-jnp", intree.backup_arena.lower(
            cfg, arena, active, sel, i32(G, P), i32(G, P))),
        ("backup-masked", intree.backup_arena.lower(
            cfg, arena, active, sel, i32(G, P), i32(G, P), False, True,
            jax.ShapeDtypeStruct((G, P), jnp.bool_))),
        ("backup-pallas", kops.backup_arena.lower(
            cfg, arena, active, sel, i32(G, P), i32(G, P))),
        ("best-action", intree.best_root_action_arena.lower(arena)),
        ("read-rows", read_slot_rows.lower(arena, np.int32(0), 512)),
        ("write-rows", write_slot_rows.lower(arena, np.int32(0), blocks)),
        ("reset-rows", reset_slot_rows.lower(arena, np.int32(0),
                                             np.int32(1), 512)),
    ]
    return out


@pytest.mark.parametrize("Fp", [8, 64])
def test_no_program_holds_an_unpacked_arena_shape(Fp):
    cfg = TreeConfig(X=600, F={8: 6, 64: 36}[Fp], D=4)
    assert cfg.Fp == Fp
    banned = re.compile("|".join(_unpacked_shapes(cfg)))
    for name, lowered in _programs(cfg):
        text = lowered.compile().as_text()
        hits = [line.strip()[:160] for line in text.splitlines()
                if banned.search(line)]
        assert not hits, f"{name}: {hits[:3]}"
