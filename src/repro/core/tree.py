"""UCT data structure (paper §III-A).

The paper decomposes the MCTS tree into the UCT (node/edge statistics,
accelerator SRAM) and the ST (environment states, host DRAM).  This module
is the UCT: a fixed-capacity struct-of-arrays holding every statistic the
in-tree operations touch, and nothing application-specific.

Layout notes (TPU adaptation of the paper's per-level SRAM banks):
  * all edge arrays are ``[X, Fp]`` with ``Fp`` = F rounded up to a power of
    two (<= 128) so a node's edge block never straddles a 128-lane VMEM row
    when flattened — see kernels/uct_select.py;
  * node ids are allocated in insertion order, which for the BSP execution
    model means ids are also grouped by superstep; the paper's level-bank
    partitioning is recovered through ``node_depth`` (used by the resource
    report, Table I analogue);
  * edge value sums (``edge_W``) and priors (``edge_P``) are stored in
    Qm.16 fixed point (paper §IV-C) so every in-tree update is an integer
    add — exact, commutative, and bit-reproducible across the numpy oracle,
    the jit batched ops, and the Pallas kernels.

Capacity is allocated for ``X`` nodes (the paper statically allocates banks
for a full F-ary tree of height D; with F=36/D=5 a full tree is ~60M nodes
against X=48K actually reachable, so we keep the X cap — the full-tree
allocation is an FPGA synthesis constraint with no TPU benefit).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import fixedpoint as fx

NULL = -1  # sentinel child / node index


def pad_fanout(f: int) -> int:
    """Round F up to a power of two <= 128 (VMEM row alignment)."""
    if f > 128:
        raise NotImplementedError(f"fanout {f} > 128: multi-row edge blocks not implemented")
    p = 1
    while p < f:
        p <<= 1
    return p


@dataclasses.dataclass(frozen=True)
class TreeConfig:
    """Static configuration of the in-tree machinery.

    vl_mode:
      * "wu"       — WU-UCT visit-count virtual loss [Liu et al., ICLR'20]:
                     incomplete-visit counters enter both uct terms.
      * "constant" — constant virtual loss [Chaslot et al. '08]: a fixed
                     penalty per in-flight worker is subtracted from the
                     edge weight (paper Alg. 1 line 5 semantics).
    score_fn:
      * "uct"  — Eq. 1 of the paper.
      * "puct" — AlphaZero-style prior-weighted variant (the paper's Gomoku
                 benchmark [9] uses a policy-value DNN; PUCT is its native
                 score).
    leaf_mode:
      * "partial"    — a node is a selection leaf while any child is
                       unexpanded (paper §II-A definition).
      * "unexpanded" — a node is a leaf until its first expansion; used with
                       expand_all=True (Gomoku benchmark expands all F
                       children at once, paper §V-A).
    """

    X: int
    F: int
    D: int
    beta: float = 1.0
    vl_mode: str = "wu"
    vl_const: float = 1.0
    score_fn: str = "uct"
    leaf_mode: str = "partial"
    expand_all: bool = False

    def __post_init__(self):
        assert self.vl_mode in ("wu", "constant"), self.vl_mode
        assert self.score_fn in ("uct", "puct"), self.score_fn
        assert self.leaf_mode in ("partial", "unexpanded"), self.leaf_mode
        assert self.X >= 2 and self.F >= 1 and self.D >= 1

    @property
    def Fp(self) -> int:
        return pad_fanout(self.F)

    @property
    def vl_const_fx(self) -> int:
        return fx.encode_scalar(self.vl_const)

    def sram_bytes(self) -> dict:
        """Table I analogue: bytes of accelerator memory per component."""
        edge_arrays = 4 + (1 if self.score_fn == "puct" else 0)  # child,N,W,VL(,P)
        node_arrays = 5  # node_N, node_O, num_expanded, num_actions, node_depth
        per_edge = 4 * edge_arrays
        per_node = 4 * node_arrays
        return {
            "edge_bytes": self.X * self.Fp * per_edge,
            "node_bytes": self.X * per_node,
            "log_table_bytes": 4 * (self.X + 2),
            "total_bytes": self.X * self.Fp * per_edge + self.X * per_node + 4 * (self.X + 2),
        }


def bucket_key(cfg: TreeConfig) -> tuple:
    """Canonical arena-pool bucket of a config (service frontend routing).

    Two configs share a pool iff every field that can change a slot's bit
    evolution matches.  The only padding that is semantics-free is the
    fanout: ``F`` enters the device programs solely through the ``Fp``
    edge-array layout (scoring masks by per-node ``num_actions`` from the
    env, and insert's provisional ``num_actions = F`` is overwritten by
    finalize before any read), so F=3 and F=4 requests share an Fp=4
    arena.  ``X`` and ``D`` look like shape parameters but are semantic —
    X caps the per-superstep insertion budget and the move-saturation
    check, D caps selection depth — so padding either would break the
    frontend's bit-identity contract with a dedicated single-config
    service.
    """
    return (cfg.X, cfg.Fp, cfg.D, cfg.beta, cfg.vl_mode, cfg.vl_const,
            cfg.score_fn, cfg.leaf_mode, cfg.expand_all)


def canonical_config(cfg: TreeConfig) -> TreeConfig:
    """The pool-side representative of ``cfg``'s bucket: fanout padded to
    the Fp lane width, everything semantic untouched.  ``bucket_key`` of
    the result equals ``bucket_key(cfg)``."""
    return dataclasses.replace(cfg, F=cfg.Fp)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class UCTree:
    """The UCT — everything the accelerator touches, nothing else."""

    child: Any         # [X, Fp] i32  child node id or NULL
    edge_N: Any        # [X, Fp] i32  completed visits through edge
    edge_W: Any        # [X, Fp] i32  Qm.16 sum of backed-up values
    edge_VL: Any       # [X, Fp] i32  in-flight (virtual-loss) count
    edge_P: Any        # [X, Fp] i32  Qm.16 prior (puct only; zeros otherwise)
    node_N: Any        # [X] i32      completed visits of node
    node_O: Any        # [X] i32      in-flight visits of node (WU-UCT O_s)
    num_expanded: Any  # [X] i32
    num_actions: Any   # [X] i32      legal-action count (<= F)
    node_depth: Any    # [X] i32
    terminal: Any      # [X] i32      1 if state is terminal (never internal)
    size: Any          # [] i32       next free node id
    root: Any          # [] i32
    log_table: Any     # [2X+4] f32   ln(n) table shared by all backends

    @property
    def X(self) -> int:
        return self.child.shape[0]

    @property
    def Fp(self) -> int:
        return self.child.shape[1]


def make_log_table(x: int) -> np.ndarray:
    """ln(n) lookup shared by every backend.

    Computed once in f64 then cast, so numpy-oracle / jit-jax / Pallas all
    read bit-identical values (libm ``log`` implementations may differ by an
    ulp between backends; a shared table removes that hazard — the TPU
    version of the paper's 'deterministic fixed-point compare' argument).
    Sized 2X+4 and index-clamped: node visit counts can exceed X when the
    tree is capacity-saturated but workers keep iterating.
    """
    n = np.arange(2 * x + 4, dtype=np.float64)
    with np.errstate(divide="ignore"):
        t = np.log(n)
    t[0] = 0.0
    return t.astype(np.float32)


def init_rows(Fp: int, R: int, root_num_actions, xp=jnp) -> dict:
    """The first R rows of a fresh single-root tree, with its `size` and
    `root`: NULL children, zero statistics, `root_num_actions` legal
    actions at node 0.  Traceable with xp=jnp (`root_num_actions` may be
    a tracer)."""
    i32 = xp.int32
    z_e = xp.zeros((R, Fp), dtype=i32)
    num_actions = xp.zeros((R,), dtype=i32)
    if xp is np:
        num_actions[0] = root_num_actions
        size, root = np.int32(1), np.int32(0)
    else:
        num_actions = num_actions.at[0].set(root_num_actions)
        size, root = xp.asarray(1, dtype=i32), xp.asarray(0, dtype=i32)
    return dict(
        child=xp.full((R, Fp), NULL, dtype=i32),
        edge_N=z_e,
        edge_W=z_e,
        edge_VL=z_e,
        edge_P=z_e,
        node_N=xp.zeros((R,), dtype=i32),
        node_O=xp.zeros((R,), dtype=i32),
        num_expanded=xp.zeros((R,), dtype=i32),
        num_actions=num_actions,
        node_depth=xp.zeros((R,), dtype=i32),
        terminal=xp.zeros((R,), dtype=i32),
        size=size,
        root=root,
    )


def init_tree(cfg: TreeConfig, root_num_actions: int | None = None, xp=jnp) -> UCTree:
    """Fresh tree with a single root node (id 0)."""
    na = cfg.F if root_num_actions is None else int(root_num_actions)
    return UCTree(**init_rows(cfg.Fp, cfg.X, na, xp),
                  log_table=xp.asarray(make_log_table(cfg.X)))


def to_numpy(tree: UCTree) -> UCTree:
    return jax.tree.map(np.asarray, tree)


def to_jax(tree: UCTree) -> UCTree:
    return jax.tree.map(jnp.asarray, tree)


# --------------------------------------------------------------------------
# Tree arena: G independent UCTrees stacked into one pytree (service layer)
# --------------------------------------------------------------------------
#
# Every leaf gains a leading [G] axis, so the whole arena is still a UCTree
# and the batched in-tree ops of intree.py apply per slot under jax.vmap
# (see intree.select_arena etc.).  The log table is identical across slots
# but stacked anyway: a uniform layout keeps vmap in_axes trivial, and at
# f32[G, 2X+4] the duplication is noise next to the edge arrays.

def stack_trees(trees: list) -> UCTree:
    """Stack G single trees into one arena pytree (leading [G] axis)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def init_arena(cfg: TreeConfig, G: int, root_num_actions: int | None = None) -> UCTree:
    """Arena of G fresh single-root trees."""
    one = init_tree(cfg, root_num_actions)
    return jax.tree.map(lambda a: jnp.broadcast_to(a, (G,) + a.shape), one)


def arena_slot(arena: UCTree, g: int) -> UCTree:
    """Extract slot g as a single UCTree view."""
    return jax.tree.map(lambda a: a[g], arena)


def arena_set_slot(arena: UCTree, g: int, tree: UCTree) -> UCTree:
    """Functionally write a single tree into slot g."""
    return jax.tree.map(lambda a, v: a.at[g].set(v), arena, tree)


def where_trees(mask, new: UCTree, old: UCTree) -> UCTree:
    """Per-slot select between two arenas: mask[g] picks new slot g.

    Used by the arena ops to make idle slots no-ops: the vmapped op runs on
    every slot (uniform device program) and this post-select discards the
    updates of inactive ones.
    """
    def pick(a, b):
        m = jnp.reshape(jnp.asarray(mask), mask.shape + (1,) * (a.ndim - 1))
        return jnp.where(m, a, b)
    return jax.tree.map(pick, new, old)


# --------------------------------------------------------------------------
# Row-bounded slot access (move commit, admission)
# --------------------------------------------------------------------------
#
# Node ids are allocated contiguously from 0, so a slot's live rows are
# [0, size), and every row at or above `size` holds its initial value:
# insertion writes only `child`, `node_depth` and `num_actions` of a new
# row and relies on its statistics being zero and its children NULL.  A
# read, write or reset therefore touches only a bucket of R rows covering
# the sizes involved: the next power of two, at least ROW_FLOOR, at most
# X.  Each is one compiled program per R with the slot index traced; the
# rows cross as one block of the edge arrays, one of the node arrays and
# the pair (size, root).  `log_table` depends on X alone and is never
# read, written or reset here.

ROW_KEYS = ("child", "edge_N", "edge_W", "edge_VL", "edge_P", "node_N",
            "node_O", "num_expanded", "num_actions", "node_depth",
            "terminal")
_EDGE_KEYS, _NODE_KEYS = ROW_KEYS[:5], ROW_KEYS[5:]
# A bucket's first use compiles its three programs, and each compiled
# bucket keeps about 0.6 MB of program on a TPU v5e, so the floor sits
# where the common trees never leave it: Pong's live trees at 128
# simulations a move hold about 90-200 nodes.
ROW_FLOOR = 512


def row_bucket(rows: int, X: int) -> int:
    """Rows a slot operation touches to cover `rows` live rows."""
    return min(X, max(ROW_FLOOR, 1 << max(int(rows) - 1, 0).bit_length()))


def pack_rows(rows: dict, xp=np) -> tuple:
    """Rows dict (R rows of ROW_KEYS, `size`, `root`) -> the three int32
    blocks that cross: edges [5, R, Fp], nodes [6, R], (size, root)."""
    i32 = xp.int32
    return (xp.stack([xp.asarray(rows[k], i32) for k in _EDGE_KEYS]),
            xp.stack([xp.asarray(rows[k], i32) for k in _NODE_KEYS]),
            xp.stack([xp.asarray(rows["size"], i32),
                      xp.asarray(rows["root"], i32)]))


def unpack_rows(blocks) -> dict:
    """Inverse of pack_rows (views into the blocks, numpy or traced)."""
    edges, nodes, head = blocks
    rows = {k: edges[i] for i, k in enumerate(_EDGE_KEYS)}
    rows.update({k: nodes[i] for i, k in enumerate(_NODE_KEYS)})
    rows["size"], rows["root"] = head[0], head[1]
    return rows


def pad_rows(cfg: TreeConfig, rows: dict) -> dict:
    """Full-width numpy snapshot of a slot from its first R rows: the
    rows above hold their initial values (the invariant above)."""
    full = init_rows(cfg.Fp, cfg.X, 0, np)
    R = len(rows["child"])
    for k in ROW_KEYS:
        full[k] = np.array(full[k])   # init_rows shares one zero block
        full[k][:R] = rows[k]
    full["size"] = np.asarray(rows["size"], np.int32)
    full["root"] = np.asarray(rows["root"], np.int32)
    full["log_table"] = make_log_table(cfg.X)
    return full


def _put_rows(arena: UCTree, g, rows: dict) -> UCTree:
    def put(a, r):
        return lax.dynamic_update_slice(
            a, r[None].astype(a.dtype), (g,) + (0,) * (a.ndim - 1))
    new = {k: put(getattr(arena, k), rows[k]) for k in ROW_KEYS}
    new["size"] = arena.size.at[g].set(rows["size"])
    new["root"] = arena.root.at[g].set(rows["root"])
    return dataclasses.replace(arena, **new)


@functools.partial(jax.jit, static_argnums=2)
def read_slot_rows(arena: UCTree, g, R: int) -> tuple:
    """Slot g's first R rows, `size` and `root` (pack_rows' blocks)."""
    def take(a):
        start = (g,) + (0,) * (a.ndim - 1)
        return lax.dynamic_slice(a, start, (1, R) + a.shape[2:])[0]
    rows = {k: take(getattr(arena, k)) for k in ROW_KEYS}
    rows["size"], rows["root"] = arena.size[g], arena.root[g]
    return pack_rows(rows, jnp)


@jax.jit
def write_slot_rows(arena: UCTree, g, blocks: tuple) -> UCTree:
    """Write pack_rows' blocks of R rows, `size` and `root` into slot g;
    rows at or above R are left as they are."""
    return _put_rows(arena, g, unpack_rows(blocks))


@functools.partial(jax.jit, static_argnums=3)
def reset_slot_rows(arena: UCTree, g, root_num_actions, R: int) -> UCTree:
    """Reset slot g's first R rows to a fresh single-root tree."""
    return _put_rows(arena, g, init_rows(arena.child.shape[2], R,
                                         root_num_actions, jnp))
