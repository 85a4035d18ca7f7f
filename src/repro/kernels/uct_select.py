"""Pallas TPU kernel: Tree-Parallel Selection + virtual-loss apply.

This is the accelerator core of the paper (§IV-B/C/D) adapted to TPU:

  paper FPGA                         | this kernel
  -----------------------------------+----------------------------------
  per-level SRAM banks, 1-cycle read | UCT packed row-aligned in VMEM
  subtree pipelines (1 worker/stage) | fori_loop over workers: identical
                                     | ordering semantics, VMEM-resident
  CLUT comparator tree at the root   | masked 128-lane VPU argmax
  fixed-point single-cycle compare   | Qm.16 int32 scores (exact compare)
  backup memoization buffer          | path_nodes/path_actions outputs

The whole UCT (all edge/node statistic arrays) is one VMEM working set —
"T_mem = 1 cycle" becomes "zero HBM traffic after tile load".  Worker
ordering is preserved exactly (worker k sees the virtual loss of workers
< k), so outputs are bit-identical to the sequential CPU program; the
kernel shares the scoring spec of repro.core.scoring verbatim.

Arena-native: the kernel runs on a ``[G]`` grid — one program per tree
slot, that slot's UCT arrays block-mapped into VMEM in the packed rows
the arena stores (core/tree.PackedTree, nothing relaid out) — so G
independent searches (the service layer's arena) cost ONE kernel launch.
Per-slot scalars (root id, tree size, active flag) ride in an SMEM
scalar-prefetch operand; an inactive slot's program is a no-op (the
aliased buffers pass through untouched), which keeps parked trees
bit-frozen.  Single-tree selection is the G=1 case.

The kernel is written for the TPU backend (2-D iotas, row-granular RMW,
power-of-two edge blocks, masked-sum lane extraction); the same kernel
runs in the Pallas interpreter on the CPU backend (kernels/ops.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import scoring
from repro.core.tree import NULL, PackedTree, TreeConfig
from repro.kernels import common as cm

LANES = cm.LANES

# meta layout: one SMEM row of per-slot scalars, prefetched before the
# grid program runs (paper: the accelerator's per-tree control registers).
# META_SIZE is reserved: the kernels read the whole block-mapped slot, but
# the TPU build will use the live tree size to bound the DMA'd prefix of
# the statistic arrays instead of shipping all X rows per slot.
META_ROOT, META_SIZE, META_ACTIVE = 0, 1, 2
META_WORDS = 3


def _select_kernel(
    # scalar prefetch
    meta_ref,        # [G, 3] i32 in SMEM: (root, size, active) per slot
    # inputs (per-slot VMEM blocks)
    child_ref,       # [Er, 128] i32 packed edges
    edge_n_ref,      # [Er, 128] i32
    edge_w_ref,      # [Er, 128] i32 (Qm.16)
    edge_p_ref,      # [Er, 128] i32 (Qm.16)
    node_n_ref,      # [Nr, 128] i32 packed nodes
    num_exp_ref,     # [Nr, 128] i32
    num_act_ref,     # [Nr, 128] i32
    terminal_ref,    # [Nr, 128] i32
    log_ref,         # [Lr, 128] f32 packed ln table
    evl_in_ref,      # [Er, 128] i32 (aliased with edge_vl_ref)
    no_in_ref,       # [Nr, 128] i32 (aliased with node_o_ref)
    # outputs (per-slot VMEM blocks)
    edge_vl_ref,     # [Er, 128] i32
    node_o_ref,      # [Nr, 128] i32
    pn_ref,          # [p, D] i32
    pa_ref,          # [p, D] i32
    depth_ref,       # [1, p] i32
    leaf_ref,        # [1, p] i32
    *,
    cfg: TreeConfig,
    p: int,
):
    Fp, D = cfg.Fp, cfg.D
    lane = cm.lane_iota()
    i32 = jnp.int32
    g = pl.program_id(0)
    root = meta_ref[g, META_ROOT]
    slot_active = meta_ref[g, META_ACTIVE]

    # Aliased buffers: physically a no-op copy; keeps the kernel correct
    # when run un-aliased (e.g. some interpret configurations).
    edge_vl_ref[...] = evl_in_ref[...]
    node_o_ref[...] = no_in_ref[...]
    # init path outputs to NULL
    pn_ref[...] = jnp.full((p, D), NULL, i32)
    pa_ref[...] = jnp.full((p, D), NULL, i32)
    depth_ref[...] = jnp.zeros((1, p), i32)
    leaf_ref[...] = jnp.zeros((1, p), i32)

    def worker(j, _):
        cm.sadd(node_o_ref, root, 1)

        def level(d, carry):
            node, depth = carry
            n_exp = cm.sload(num_exp_ref, node)
            n_act = cm.sload(num_act_ref, node)
            term = cm.sload(terminal_ref, node)
            leafp = scoring.is_leaf(
                cfg, num_expanded=n_exp, num_actions=n_act,
                terminal=term, depth=depth, xp=jnp)
            active = (~leafp) & (d == depth)

            row = node * Fp // LANES
            off = node * Fp % LANES
            child_r = cm.load_row(child_ref, row)
            seg = (lane >= off) & (lane < off + Fp)
            child_m = jnp.where(seg, child_r, NULL)

            n_n = cm.sload(node_n_ref, node)
            n_o = cm.sload(node_o_ref, node)
            log_ns = cm.sload(log_ref, scoring.log_index(cfg, n_n, n_o, jnp))

            scores = scoring.edge_scores_fx(
                cfg,
                child=child_m,
                edge_N=cm.load_row(edge_n_ref, row),
                edge_W=cm.load_row(edge_w_ref, row),
                edge_VL=cm.load_row(edge_vl_ref, row),
                edge_P=cm.load_row(edge_p_ref, row),
                node_N=n_n[None, None],
                node_O=n_o[None, None],
                num_actions=(off + n_act)[None, None],
                xp=jnp,
                lane=lane,                      # lane < off + n_act validity
                log_ns=log_ns[None, None],
            )
            # VPU-native worker distributor (paper's CLUT, §IV-D): masked
            # first-max argmax over the full 128-lane row, as two 2-D
            # reductions (max, then min-index-of-max) — Mosaic-friendly.
            m = jnp.max(scores)
            g_ = jnp.min(jnp.where(scores == m, lane, i32(LANES))).astype(i32)

            # virtual-loss apply (Alg. 1 line 5) — row RMW
            vl_row = cm.load_row(edge_vl_ref, row)
            inc = jnp.where(active & (lane == g_), i32(1), i32(0))
            cm.store_row(edge_vl_ref, row, vl_row + inc)

            # memoization buffer write (paper §IV-E)
            d_lane = jax.lax.broadcasted_iota(i32, (1, D), 1)
            pn_row = cm.load_row(pn_ref, j)
            pa_row = cm.load_row(pa_ref, j)
            sel_d = active & (d_lane == d)
            cm.store_row(pn_ref, j, jnp.where(sel_d, node, pn_row))
            cm.store_row(pa_ref, j, jnp.where(sel_d, g_ - off, pa_row))

            nxt = cm.extract_lane(child_m, g_)
            node = jnp.where(active, nxt, node)
            cm.sadd(node_o_ref, node, jnp.where(active, i32(1), i32(0)))
            depth = depth + jnp.where(active, i32(1), i32(0))
            return node, depth

        node, depth = jax.lax.fori_loop(0, D, level, (root, i32(0)))
        sel_j = jax.lax.broadcasted_iota(i32, (1, p), 1) == j
        depth_ref[...] = jnp.where(sel_j, depth, depth_ref[...])
        leaf_ref[...] = jnp.where(sel_j, node, leaf_ref[...])
        return 0

    # inactive slot -> no-op program: the pass-through copies above leave
    # the tree statistics bit-identical and the path outputs are dead rows
    @pl.when(slot_active == 1)
    def _run_workers():
        jax.lax.fori_loop(0, p, worker, 0)


@functools.partial(jax.jit, static_argnames=("cfg", "p", "interpret"))
def select_arena(cfg: TreeConfig, arena: PackedTree, active, p: int,
                 interpret: bool):
    """Selection kernel over a G-slot arena (one grid program per slot).

    `arena` is a PackedTree whose leaves carry a leading [G] axis, passed
    to the kernel as it is stored; `active` is a [G] mask (bool or i32).
    Returns (edge_VL', node_O', path_nodes, path_actions, depths, leaves):
    the two statistics packed as the arena stores them, updated in place,
    the paths [G, p, D] / [G, p].  Inactive slots come back bit-identical
    with NULL/zero path rows.
    """
    D = cfg.D
    G, er, _ = arena.child.shape
    nr, lr = arena.node_N.shape[1], arena.log_table.shape[1]
    meta = jnp.stack(
        [jnp.asarray(arena.root, jnp.int32),
         jnp.asarray(arena.size, jnp.int32),
         jnp.asarray(active, jnp.int32)], axis=1)          # [G, 3]

    slot = lambda *shp: pl.BlockSpec((None,) + shp,
                                     lambda g, m: (g,) + (0,) * len(shp))
    out_shapes = (
        jax.ShapeDtypeStruct((G, er, LANES), jnp.int32),   # edge_VL'
        jax.ShapeDtypeStruct((G, nr, LANES), jnp.int32),   # node_O'
        jax.ShapeDtypeStruct((G, p, D), jnp.int32),        # path_nodes
        jax.ShapeDtypeStruct((G, p, D), jnp.int32),        # path_actions
        jax.ShapeDtypeStruct((G, 1, p), jnp.int32),        # depths
        jax.ShapeDtypeStruct((G, 1, p), jnp.int32),        # leaves
    )
    kernel = functools.partial(_select_kernel, cfg=cfg, p=p)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(G,),
        in_specs=[
            slot(er, LANES), slot(er, LANES), slot(er, LANES),
            slot(er, LANES),
            slot(nr, LANES), slot(nr, LANES), slot(nr, LANES),
            slot(nr, LANES), slot(lr, LANES),
            slot(er, LANES), slot(nr, LANES),
        ],
        out_specs=[
            slot(er, LANES), slot(nr, LANES),
            slot(p, D), slot(p, D), slot(1, p), slot(1, p),
        ],
    )
    # input indices count the scalar-prefetch operand (meta = 0)
    evl2, no2, pn, pa, dep, leaf = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shapes,
        input_output_aliases={10: 0, 11: 1},
        name="select_arena",   # the op name traces and readers match
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=cm.VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(meta, arena.child, arena.edge_N, arena.edge_W, arena.edge_P,
      arena.node_N, arena.num_expanded, arena.num_actions, arena.terminal,
      arena.log_table, arena.edge_VL, arena.node_O)
    return evl2, no2, pn, pa, dep[:, 0], leaf[:, 0]
