"""Per-kernel sweeps: Pallas uct_select / uct_backup vs the pure-jnp oracle
(kernels/ref.py), bit-exact, across fanouts / depths / worker counts /
scoring variants.  On the CPU backend kernels/ops.py runs the kernels in
the Pallas interpreter; test_tpu_compile compiles them for a TPU v5e.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import TreeConfig, init_tree, intree, fixedpoint as fx
from repro.core.tree import (
    pack_edges, pack_nodes, pack_tree, to_jax, unpack_edges, unpack_nodes,
    unpack_tree,
)
from repro.kernels import ops as kops
from repro.kernels import ref as kref


def _arena(tree):
    """A single tree as the G=1 arena the kernels run on."""
    return jax.tree.map(lambda a: jnp.asarray(a)[None], tree)


def _slot0(tree):
    return jax.tree.map(lambda a: a[0], tree)


def grow_tree(cfg, supersteps=3, p=6, seed=0):
    """Grow a random-valued tree with the oracle jnp ops to get a
    non-trivial UCT state."""
    rng = np.random.RandomState(seed)
    tree = to_jax(pack_tree(init_tree(cfg, xp=np)))
    for _ in range(supersteps):
        tree, sel = kref.select_ref(cfg, tree, p)
        tree, new_nodes = intree.insert_batch(cfg, tree, sel)
        sim_nodes = np.where(np.asarray(sel.expand_action) >= 0,
                             np.asarray(new_nodes[:, 0]),
                             np.asarray(sel.leaves)).astype(np.int32)
        vals = fx.encode(rng.uniform(-1, 1, p).astype(np.float32))
        tree = kref.backup_ref(cfg, tree, sel, jnp.asarray(sim_nodes),
                               jnp.asarray(np.asarray(vals)), False)
    return tree


TREE_SWEEP = [
    TreeConfig(X=64, F=2, D=3),
    TreeConfig(X=128, F=4, D=5),
    TreeConfig(X=128, F=6, D=4, vl_mode="constant", vl_const=0.5),
    TreeConfig(X=256, F=36, D=3, score_fn="puct", leaf_mode="unexpanded",
               expand_all=True),
]


@pytest.mark.parametrize("cfg", TREE_SWEEP,
                         ids=lambda c: f"F{c.F}-D{c.D}-{c.vl_mode}-{c.score_fn}")
@pytest.mark.parametrize("p", [1, 4, 16])
def test_select_kernel_matches_ref(cfg, p):
    tree = grow_tree(cfg, supersteps=2, p=4)
    t_ref, sel_ref = kref.select_ref(cfg, tree, p)
    t_k, sel_k = kops.select_arena(cfg, _arena(tree), jnp.ones(1), p)
    t_k, sel_k = _slot0(t_k), _slot0(sel_k)
    t_ref, t_k = unpack_tree(t_ref), unpack_tree(t_k)
    np.testing.assert_array_equal(t_ref.edge_VL, t_k.edge_VL)
    np.testing.assert_array_equal(t_ref.node_O, t_k.node_O)
    for f in ("path_nodes", "path_actions", "depths", "leaves",
              "expand_action", "n_insert", "insert_base"):
        np.testing.assert_array_equal(
            np.asarray(getattr(sel_ref, f)), np.asarray(getattr(sel_k, f)),
            err_msg=f)


@pytest.mark.parametrize("cfg", TREE_SWEEP[:3],
                         ids=lambda c: f"F{c.F}-D{c.D}-{c.vl_mode}")
@pytest.mark.parametrize("alternating", [False, True])
def test_backup_kernel_matches_ref(cfg, alternating):
    p = 6
    rng = np.random.RandomState(1)
    tree = grow_tree(cfg, supersteps=2, p=4)
    tree, sel = kref.select_ref(cfg, tree, p)
    tree, new_nodes = intree.insert_batch(cfg, tree, sel)
    sim_nodes = jnp.where(sel.expand_action >= 0, new_nodes[:, 0], sel.leaves)
    vals = jnp.asarray(np.asarray(
        fx.encode(rng.uniform(-1, 1, p).astype(np.float32))))

    t_ref = kref.backup_ref(cfg, tree, sel, sim_nodes, vals, alternating)
    t_k = _slot0(kops.backup_arena(
        cfg, _arena(tree), jnp.ones(1), _arena(sel), sim_nodes[None],
        vals[None], alternating))
    t_ref, t_k = unpack_tree(t_ref), unpack_tree(t_k)
    for f in ("edge_N", "edge_W", "edge_VL", "node_N", "node_O"):
        np.testing.assert_array_equal(
            getattr(t_ref, f), getattr(t_k, f), err_msg=f)


def test_packing_roundtrip():
    rng = np.random.RandomState(0)
    for x, f in [(64, 2), (100, 4), (48, 36), (128, 128)]:
        fp = 1
        while fp < f:
            fp *= 2
        arr = rng.randint(0, 100, (x, fp)).astype(np.int32)
        packed = pack_edges(arr)
        assert packed.shape[1] == 128
        np.testing.assert_array_equal(unpack_edges(packed, x, fp), arr)
        node = rng.randint(0, 100, (x,)).astype(np.int32)
        np.testing.assert_array_equal(unpack_nodes(pack_nodes(node), x),
                                      node)
