"""Pallas TPU kernel: BackUp from memoized selection paths (paper §IV-E).

The paper attaches a (D-1)-word memoization buffer to each worker during
Selection so BackUp never re-walks the tree; the FPGA then streams workers
through the pipeline, updating one level per stage.  Here the memoized
paths arrive as the `path_nodes`/`path_actions` arrays produced by the
selection kernel, and every update is an exact Qm.16 integer add performed
as a full-row VMEM read-modify-write.

Arena-native like the selection kernel: a ``[G]`` grid maps one program to
each tree slot (its packed statistic arrays block-mapped into VMEM, its
scalars — here just the active flag — scalar-prefetched in SMEM), so all
G trees back up in one launch and an inactive slot's program is a no-op
pass-through.  Single-tree backup is the G=1 case.

Integer adds commute, so although this kernel loops workers in order (to
mirror the paper's pipeline), the result is independent of worker order —
the property the vectorized jnp fallback (core.intree.backup_batch)
exploits; both are bit-identical to the sequential CPU program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.tree import NULL, TreeConfig
from repro.kernels import common as cm
from repro.kernels.uct_select import META_ACTIVE, META_WORDS

LANES = cm.LANES


def _backup_kernel(
    # scalar prefetch
    meta_ref,      # [G, 3] i32 in SMEM: (root, size, active) per slot
    # inputs (per-slot blocks)
    pn_ref,        # [p, D] i32 memoized path nodes
    pa_ref,        # [p, D] i32 memoized path actions
    depth_ref,     # [1, p] i32
    leaf_ref,      # [1, p] i32
    ea_ref,        # [1, p] i32 expand_action
    simn_ref,      # [1, p] i32 sim nodes
    val_ref,       # [1, p] i32 Qm.16 values
    en_in_ref, ew_in_ref, evl_in_ref, nn_in_ref, no_in_ref,   # aliased ins
    # outputs (aliased)
    edge_n_ref,    # [Er, 128] i32
    edge_w_ref,    # [Er, 128] i32
    edge_vl_ref,   # [Er, 128] i32
    node_n_ref,    # [Nr, 128] i32
    node_o_ref,    # [Nr, 128] i32
    *,
    cfg: TreeConfig,
    p: int,
    alternating: bool,
):
    Fp, D = cfg.Fp, cfg.D
    i32 = jnp.int32
    lane = cm.lane_iota()
    g = pl.program_id(0)
    slot_active = meta_ref[g, META_ACTIVE]

    edge_n_ref[...] = en_in_ref[...]
    edge_w_ref[...] = ew_in_ref[...]
    edge_vl_ref[...] = evl_in_ref[...]
    node_n_ref[...] = nn_in_ref[...]
    node_o_ref[...] = no_in_ref[...]

    def row_of(x):  # [1,p] ref scalar extraction
        return lambda j: cm.extract_lane(x[...], j)

    get_depth, get_leaf = row_of(depth_ref), row_of(leaf_ref)
    get_ea, get_sim, get_val = row_of(ea_ref), row_of(simn_ref), row_of(val_ref)

    def worker(j, _):
        depth = get_depth(j)
        leaf = get_leaf(j)
        ea = get_ea(j)
        sim = get_sim(j)
        v = get_val(j)
        expanded = (ea >= 0) & jnp.asarray(not cfg.expand_all)
        sim_depth = depth + jnp.where(expanded, i32(1), i32(0))

        def level(d, _):
            pn_row = cm.load_row(pn_ref, j)
            pa_row = cm.load_row(pa_ref, j)
            node = cm.extract_lane(pn_row, d)
            a = cm.extract_lane(pa_row, d)
            on = (d < depth) & (node != NULL)
            node = jnp.where(on, node, i32(0))   # keep addresses in-bounds
            a = jnp.where(on, a, i32(0))         # (masked updates below)
            inc = jnp.where(on, i32(1), i32(0))
            if alternating:
                sign = jnp.where((sim_depth - d) % 2 == 1, i32(-1), i32(1))
            else:
                sign = i32(1)
            row = node * Fp // LANES
            tgt = (lane == node * Fp % LANES + a)
            upd = jnp.where(tgt, inc, i32(0))
            cm.store_row(edge_n_ref, row, cm.load_row(edge_n_ref, row) + upd)
            cm.store_row(edge_w_ref, row,
                         cm.load_row(edge_w_ref, row) + upd * sign * v)
            cm.store_row(edge_vl_ref, row,
                         cm.load_row(edge_vl_ref, row) - upd)
            cm.sadd(node_n_ref, node, inc)
            cm.sadd(node_o_ref, node, -inc)
            return 0

        jax.lax.fori_loop(0, D, level, 0)
        cm.sadd(node_n_ref, leaf, 1)
        cm.sadd(node_o_ref, leaf, -1)

        # expansion edge (single-expand mode): seed sim node's in-edge
        e_inc = jnp.where(expanded, i32(1), i32(0))
        if alternating:
            e_sign = jnp.where((sim_depth - depth) % 2 == 1, i32(-1), i32(1))
        else:
            e_sign = i32(1)
        row = leaf * Fp // LANES
        tgt = lane == leaf * Fp % LANES + ea
        upd = jnp.where(tgt, e_inc, i32(0))
        cm.store_row(edge_n_ref, row, cm.load_row(edge_n_ref, row) + upd)
        cm.store_row(edge_w_ref, row,
                     cm.load_row(edge_w_ref, row) + upd * e_sign * v)
        cm.sadd(node_n_ref, jnp.where(expanded, sim, leaf),
                jnp.where(expanded, i32(1), i32(0)))
        return 0

    # inactive slot -> no-op program (pass-through copies only)
    @pl.when(slot_active == 1)
    def _run_workers():
        jax.lax.fori_loop(0, p, worker, 0)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "p", "alternating", "interpret"))
def backup_arena(cfg: TreeConfig, arena, active, pn, pa, depths, leaves,
                 expand_action, sim_nodes, values_fx, p: int,
                 alternating: bool, interpret: bool):
    """Backup kernel over a G-slot arena.  All per-worker inputs carry a
    leading [G] axis ([G, p, D] paths, [G, p] scalars); `active` is a [G]
    mask.  Returns updated (edge_N, edge_W, edge_VL, node_N, node_O) in
    logical shapes [G, X, Fp] / [G, X]; inactive slots are bit-identical.
    """
    Fp = cfg.Fp
    G, X = arena.child.shape[0], arena.child.shape[1]
    en_p = cm.pack_edges_arena(arena.edge_N, Fp)
    ew_p = cm.pack_edges_arena(arena.edge_W, Fp)
    evl_p = cm.pack_edges_arena(arena.edge_VL, Fp)
    nn_p = cm.pack_nodes_arena(arena.node_N)
    no_p = cm.pack_nodes_arena(arena.node_O)
    er, nr = en_p.shape[1], nn_p.shape[1]
    D = cfg.D
    meta = jnp.zeros((G, META_WORDS), jnp.int32)
    meta = meta.at[:, META_ACTIVE].set(jnp.asarray(active, jnp.int32))

    slot = lambda *shp: pl.BlockSpec((None,) + shp,
                                     lambda g, m: (g,) + (0,) * len(shp))
    out_shapes = tuple(
        jax.ShapeDtypeStruct((G, er, LANES), jnp.int32) for _ in range(3)
    ) + tuple(
        jax.ShapeDtypeStruct((G, nr, LANES), jnp.int32) for _ in range(2))
    kernel = functools.partial(
        _backup_kernel, cfg=cfg, p=p, alternating=alternating)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(G,),
        in_specs=[
            slot(p, D), slot(p, D), slot(1, p), slot(1, p),
            slot(1, p), slot(1, p), slot(1, p),
            slot(er, LANES), slot(er, LANES), slot(er, LANES),
            slot(nr, LANES), slot(nr, LANES),
        ],
        out_specs=[
            slot(er, LANES), slot(er, LANES), slot(er, LANES),
            slot(nr, LANES), slot(nr, LANES),
        ],
    )
    # input indices count the scalar-prefetch operand (meta = 0)
    en2, ew2, evl2, nn2, no2 = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        input_output_aliases={8: 0, 9: 1, 10: 2, 11: 3, 12: 4},
        interpret=interpret,
    )(
        meta, pn, pa, depths.reshape(G, 1, p), leaves.reshape(G, 1, p),
        expand_action.reshape(G, 1, p), sim_nodes.reshape(G, 1, p),
        values_fx.reshape(G, 1, p),
        en_p, ew_p, evl_p, nn_p, no_p,
    )
    return (
        cm.unpack_edges_arena(en2, X, Fp),
        cm.unpack_edges_arena(ew2, X, Fp),
        cm.unpack_edges_arena(evl2, X, Fp),
        cm.unpack_nodes_arena(nn2, X),
        cm.unpack_nodes_arena(no2, X),
    )
