"""UCT data structure (paper §III-A).

The paper decomposes the MCTS tree into the UCT (node/edge statistics,
accelerator SRAM) and the ST (environment states, host DRAM).  This module
is the UCT: a fixed-capacity struct-of-arrays holding every statistic the
in-tree operations touch, and nothing application-specific.

Layout notes (TPU adaptation of the paper's per-level SRAM banks):
  * logically every edge array is ``[X, Fp]`` with ``Fp`` = F rounded up to
    a power of two (<= 128) and every node array ``[X]``: that is the
    ``UCTree`` the host, the numpy oracle and the executor API see;
  * on the device the tree is a ``PackedTree``, stored in the rows the
    Pallas kernels read: edge arrays ``[X*Fp/128, 128]`` (node n's Fp-edge
    block is lanes ``n*Fp % 128 ...`` of row ``n*Fp // 128``, so it never
    straddles a row), node arrays and the log table ``[ceil(n/128), 128]``.
    Every device program indexes those rows through the accessors below,
    so no program relays the arena out; the host converts with a numpy
    reshape (``pack_tree`` / ``unpack_tree``, ``pack_rows`` /
    ``unpack_rows``);
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
    """Canonical arena-pool bucket of a config (SchedulerCore routing).

    Two configs share a pool iff every field that can change a slot's bit
    evolution matches.  The only padding that is semantics-free is the
    fanout: ``F`` enters the device programs solely through the ``Fp``
    edge-array layout (scoring masks by per-node ``num_actions`` from the
    env, and insert's provisional ``num_actions = F`` is overwritten by
    finalize before any read), so F=3 and F=4 requests share an Fp=4
    arena.  ``X`` and ``D`` look like shape parameters but are semantic —
    X caps the per-superstep insertion budget and the move-saturation
    check, D caps selection depth — so padding either would break the
    client's bit-identity contract with a dedicated single-config
    ArenaPool.
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
    """The UCT — everything the accelerator touches, nothing else — in its
    logical layout: how the host, the numpy oracle and the executor API
    see a tree.  The device holds it as a PackedTree."""

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
        return self.child.shape[-2]

    @property
    def Fp(self) -> int:
        return self.child.shape[-1]


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
    """Fresh tree with a single root node (id 0), logical layout."""
    na = cfg.F if root_num_actions is None else int(root_num_actions)
    return UCTree(**init_rows(cfg.Fp, cfg.X, na, xp),
                  log_table=xp.asarray(make_log_table(cfg.X)))


def to_jax(tree):
    return jax.tree.map(jnp.asarray, tree)


# --------------------------------------------------------------------------
# The packed layout: what the device stores
# --------------------------------------------------------------------------
#
# A row of 128 int32 lanes holds 128/Fp nodes' edge blocks, or 128 entries
# of a node array.  Each array holds whole (8, 128) tiles: XLA keeps an
# array of whole tiles in the row-major layout the kernels read, where it
# would store a [G, rows, 128] arena with a partial tile G-minor around
# its scatters and copy it at every kernel call.  Lanes past the last node
# are padding that holds initial values (NULL children, zeros) and is
# never read.  Packing and unpacking is a numpy reshape on the host; a
# device program never converts.

LANES = 128
SUBLANES = 8
ROW_KEYS = ("child", "edge_N", "edge_W", "edge_VL", "edge_P", "node_N",
            "node_O", "num_expanded", "num_actions", "node_depth",
            "terminal")
EDGE_KEYS, NODE_KEYS = ROW_KEYS[:5], ROW_KEYS[5:]


def edge_rows(R: int, Fp: int) -> int:
    """Packed rows holding the edge blocks of nodes [0, R)."""
    return -(-R * Fp // LANES)


def node_rows(R: int) -> int:
    """Packed rows holding entries [0, R) of a node array."""
    return -(-R // LANES)


def _tiled(rows: int) -> int:
    return -(-rows // SUBLANES) * SUBLANES


def stored_rows(X: int, Fp: int) -> tuple:
    """Rows the device stores for a tree of X nodes: (edge arrays, node
    arrays, log table)."""
    return (_tiled(edge_rows(X, Fp)), _tiled(node_rows(X)),
            _tiled(node_rows(2 * X + 4)))


def _fill(key: str) -> int:
    """The initial value of a row of `key`, which padding holds too."""
    return NULL if key == "child" else 0


def pack_edges(a, rows: int | None = None, fill: int = 0) -> np.ndarray:
    """[..., R, Fp] -> [..., rows, 128] (host), `rows` at least
    edge_rows(R, Fp) (the default); lanes past node R hold `fill`."""
    a = np.asarray(a)
    R, Fp = a.shape[-2:]
    rows = edge_rows(R, Fp) if rows is None else rows
    pad = rows * LANES // Fp - R
    if pad:
        a = np.concatenate(
            [a, np.full(a.shape[:-2] + (pad, Fp), fill, a.dtype)], axis=-2)
    return a.reshape(a.shape[:-2] + (rows, LANES))


def unpack_edges(packed, R: int, Fp: int) -> np.ndarray:
    """Inverse of pack_edges: the first R nodes' [R, Fp] blocks (host)."""
    packed = np.asarray(packed)
    return packed.reshape(packed.shape[:-2] + (-1, Fp))[..., :R, :]


def pack_nodes(a, rows: int | None = None, fill: int = 0) -> np.ndarray:
    """[..., R] -> [..., rows, 128] (host), `rows` at least node_rows(R)
    (the default); entries past R hold `fill`."""
    a = np.asarray(a)
    rows = node_rows(a.shape[-1]) if rows is None else rows
    pad = rows * LANES - a.shape[-1]
    if pad:
        a = np.concatenate(
            [a, np.full(a.shape[:-1] + (pad,), fill, a.dtype)], axis=-1)
    return a.reshape(a.shape[:-1] + (rows, LANES))


def unpack_nodes(packed, R: int) -> np.ndarray:
    """Inverse of pack_nodes: entries [0, R) (host)."""
    packed = np.asarray(packed)
    return packed.reshape(packed.shape[:-2] + (-1,))[..., :R]


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=ROW_KEYS + ("size", "root", "log_table"),
                   meta_fields=("X", "Fp"))
@dataclasses.dataclass(frozen=True)
class PackedTree:
    """The UCT as the device stores it, in the rows the kernels read.
    Leaves may carry leading axes (an arena's [G]); X and Fp are static."""

    child: Any         # [Er, 128] i32; (Er, Nr, Lr) = stored_rows(X, Fp)
    edge_N: Any        # [Er, 128] i32
    edge_W: Any        # [Er, 128] i32
    edge_VL: Any       # [Er, 128] i32
    edge_P: Any        # [Er, 128] i32
    node_N: Any        # [Nr, 128] i32
    node_O: Any        # [Nr, 128] i32
    num_expanded: Any  # [Nr, 128] i32
    num_actions: Any   # [Nr, 128] i32
    node_depth: Any    # [Nr, 128] i32
    terminal: Any      # [Nr, 128] i32
    size: Any          # [] i32
    root: Any          # [] i32
    log_table: Any     # [Lr, 128] f32
    X: int
    Fp: int


def pack_tree(tree: UCTree) -> PackedTree:
    """Logical tree (any leading axes) -> packed, as numpy (host)."""
    t = jax.device_get(tree)
    er, nr, lr = stored_rows(t.X, t.Fp)
    out = {k: pack_edges(getattr(t, k), er, _fill(k)) for k in EDGE_KEYS}
    out.update({k: pack_nodes(getattr(t, k), nr) for k in NODE_KEYS})
    return PackedTree(**out, size=np.asarray(t.size),
                      root=np.asarray(t.root),
                      log_table=pack_nodes(t.log_table, lr), X=t.X, Fp=t.Fp)


def unpack_tree(packed: PackedTree) -> UCTree:
    """Packed tree (any leading axes) -> its logical view, as numpy
    (host)."""
    t = jax.device_get(packed)
    out = {k: unpack_edges(getattr(t, k), t.X, t.Fp) for k in EDGE_KEYS}
    out.update({k: unpack_nodes(getattr(t, k), t.X) for k in NODE_KEYS})
    return UCTree(**out, size=np.asarray(t.size), root=np.asarray(t.root),
                  log_table=unpack_nodes(t.log_table, 2 * t.X + 4))


def _fresh_rows(er: int, nr: int, root_num_actions) -> dict:
    """The first `er` edge rows and `nr` node rows of a fresh single-root
    tree (traceable; `root_num_actions` may be a tracer)."""
    i32 = jnp.int32
    z_e = jnp.zeros((er, LANES), i32)
    z_n = jnp.zeros((nr, LANES), i32)
    return dict(child=jnp.full(z_e.shape, NULL, i32), edge_N=z_e,
                edge_W=z_e, edge_VL=z_e, edge_P=z_e, node_N=z_n, node_O=z_n,
                num_expanded=z_n,
                num_actions=z_n.at[0, 0].set(root_num_actions),
                node_depth=z_n, terminal=z_n)


# ---- accessors: the jnp phase ops read and write the packed rows only ----
#
# `t` supplies the static X and Fp (any PackedTree of the layout, e.g. the
# tree being updated); `arr` is one of its leaves or an array of the same
# shape.  Node and action indices broadcast.  A write whose (node, action)
# lies outside [0, X) x [0, Fp) is dropped, so callers mask with an
# out-of-range id (X, or Fp for the action) as with plain scatters.

def _edge_at(t: PackedTree, node, a):
    flat = jnp.asarray(node) * t.Fp + jnp.asarray(a)
    return flat // LANES, flat % LANES


def edge_get(t: PackedTree, arr, node, a):
    """arr[node, a] of an edge array."""
    return arr[_edge_at(t, node, a)]


def edge_row(t: PackedTree, arr, node):
    """The Fp-edge block of `node`: [..., Fp]."""
    node = jnp.asarray(node)[..., None]
    return edge_get(t, arr, node, jnp.arange(t.Fp, dtype=jnp.int32))


def _edge_drop(t: PackedTree, arr, node, a):
    node, a = jnp.asarray(node), jnp.asarray(a)
    row, lane = _edge_at(t, node, a)
    inside = (node >= 0) & (node < t.X) & (a >= 0) & (a < t.Fp)
    return jnp.where(inside, row, arr.shape[-2]), lane


def edge_set(t: PackedTree, arr, node, a, val):
    return arr.at[_edge_drop(t, arr, node, a)].set(val, mode="drop")


def edge_add(t: PackedTree, arr, node, a, inc):
    return arr.at[_edge_drop(t, arr, node, a)].add(inc, mode="drop")


def node_get(arr, node):
    """arr[node] of a packed node array (or the log table)."""
    node = jnp.asarray(node)
    return arr[node // LANES, node % LANES]


def _node_drop(t: PackedTree, arr, node):
    node = jnp.asarray(node)
    inside = (node >= 0) & (node < t.X)
    return jnp.where(inside, node // LANES, arr.shape[-2]), node % LANES


def node_set(t: PackedTree, arr, node, val):
    return arr.at[_node_drop(t, arr, node)].set(val, mode="drop")


def node_add(t: PackedTree, arr, node, inc):
    return arr.at[_node_drop(t, arr, node)].add(inc, mode="drop")


# --------------------------------------------------------------------------
# Tree arena: G independent trees stacked into one pytree (service layer)
# --------------------------------------------------------------------------
#
# Every leaf gains a leading [G] axis, so the whole arena is still a
# PackedTree and the batched in-tree ops of intree.py apply per slot under
# jax.vmap (see intree.select_arena etc.).  The log table is identical
# across slots but stacked anyway: a uniform layout keeps vmap in_axes
# trivial, and at f32[G, 2X+4] the duplication is noise next to the edge
# arrays.

def init_arena(cfg: TreeConfig, G: int,
               root_num_actions: int | None = None) -> PackedTree:
    """Arena of G fresh single-root trees, built in the packed layout."""
    na = cfg.F if root_num_actions is None else int(root_num_actions)
    er, nr, lr = stored_rows(cfg.X, cfg.Fp)
    one = _fresh_rows(er, nr, na)
    one.update(size=jnp.asarray(1, jnp.int32), root=jnp.asarray(0, jnp.int32),
               log_table=jnp.asarray(pack_nodes(make_log_table(cfg.X), lr)))
    return PackedTree(**{k: jnp.broadcast_to(a, (G,) + a.shape)
                         for k, a in one.items()}, X=cfg.X, Fp=cfg.Fp)


def arena_slot(arena, g: int):
    """Extract slot g as a single tree view."""
    return jax.tree.map(lambda a: a[g], arena)


def arena_set_slot(arena, g: int, tree):
    """Functionally write a single tree into slot g."""
    return jax.tree.map(lambda a, v: a.at[g].set(v), arena, tree)


def where_trees(mask, new, old):
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
# rows cross as one block of whole packed rows of the edge arrays, one of
# the node arrays and the pair (size, root).  A bucket below X is a power
# of two of at least ROW_FLOOR = 512 nodes, so it fills exactly
# edge_rows(R, Fp) and node_rows(R) packed rows; the bucket at X covers
# every stored row, the padded tail included.  `log_table` depends on X
# alone and is never read, written or reset here.

# A bucket's first use compiles its three programs, and each compiled
# bucket keeps about 0.6 MB of program on a TPU v5e, so the floor sits
# where the common trees never leave it: Pong's live trees at 128
# simulations a move hold about 90-200 nodes.
ROW_FLOOR = 512


def row_bucket(rows: int, X: int) -> int:
    """Rows a slot operation touches to cover `rows` live rows."""
    return min(X, max(ROW_FLOOR, 1 << max(int(rows) - 1, 0).bit_length()))


def block_rows(R: int, X: int, Fp: int) -> tuple:
    """(edge rows, node rows) a bucket of R rows covers."""
    if R == X:
        return stored_rows(X, Fp)[:2]
    return edge_rows(R, Fp), node_rows(R)


def pack_rows(cfg: TreeConfig, rows: dict, R: int) -> tuple:
    """Logical rows dict (at most R rows of ROW_KEYS, `size`, `root`) ->
    the three blocks that cross for a bucket of R rows: edges
    [5, er, 128], nodes [6, nr, 128] (block_rows), (size, root).  Lanes
    past the given rows hold initial values (host)."""
    er, nr = block_rows(R, cfg.X, cfg.Fp)

    def block(k):
        a = np.asarray(rows[k], np.int32)
        return (pack_edges(a, er, _fill(k)) if a.ndim == 2
                else pack_nodes(a, nr, _fill(k)))
    return (np.stack([block(k) for k in EDGE_KEYS]),
            np.stack([block(k) for k in NODE_KEYS]),
            np.array([rows["size"], rows["root"]], np.int32))


def unpack_rows(cfg: TreeConfig, blocks, R: int) -> dict:
    """Inverse of pack_rows: R logical rows, `size` and `root` (host)."""
    edges, nodes, head = (np.asarray(b) for b in blocks)
    rows = {k: unpack_edges(edges[i], R, cfg.Fp)
            for i, k in enumerate(EDGE_KEYS)}
    rows.update({k: unpack_nodes(nodes[i], R)
                 for i, k in enumerate(NODE_KEYS)})
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


def _slot_start(g) -> tuple:
    """(g, 0, 0) in g's dtype: dynamic slice starts share one dtype, and
    a literal 0 is int64 under JAX_ENABLE_X64."""
    z = jnp.zeros_like(g)
    return g, z, z


def _put_rows(arena: PackedTree, g, rows: dict) -> PackedTree:
    def put(a, r):
        return lax.dynamic_update_slice(a, r[None].astype(a.dtype),
                                        _slot_start(g))
    new = {k: put(getattr(arena, k), rows[k]) for k in ROW_KEYS}
    new["size"] = arena.size.at[g].set(rows["size"])
    new["root"] = arena.root.at[g].set(rows["root"])
    return dataclasses.replace(arena, **new)


@functools.partial(jax.jit, static_argnums=2)
def read_slot_rows(arena: PackedTree, g, R: int) -> tuple:
    """Slot g's packed rows covering nodes [0, R), `size` and `root`
    (pack_rows' blocks)."""
    er, nr = block_rows(R, arena.X, arena.Fp)

    def take(k, n):
        a = getattr(arena, k)
        return lax.dynamic_slice(a, _slot_start(g), (1, n, LANES))[0]
    return (jnp.stack([take(k, er) for k in EDGE_KEYS]),
            jnp.stack([take(k, nr) for k in NODE_KEYS]),
            jnp.stack([arena.size[g], arena.root[g]]))


@jax.jit
def write_slot_rows(arena: PackedTree, g, blocks: tuple) -> PackedTree:
    """Write pack_rows' blocks, `size` and `root` into slot g; rows past
    the blocks are left as they are."""
    edges, nodes, head = blocks
    rows = {k: edges[i] for i, k in enumerate(EDGE_KEYS)}
    rows.update({k: nodes[i] for i, k in enumerate(NODE_KEYS)})
    rows["size"], rows["root"] = head[0], head[1]
    return _put_rows(arena, g, rows)


@functools.partial(jax.jit, static_argnums=3)
def reset_slot_rows(arena: PackedTree, g, root_num_actions,
                    R: int) -> PackedTree:
    """Reset slot g's packed rows covering nodes [0, R) to a fresh
    single-root tree."""
    rows = _fresh_rows(*block_rows(R, arena.X, arena.Fp), root_num_actions)
    rows["size"], rows["root"] = jnp.int32(1), jnp.int32(0)
    return _put_rows(arena, g, rows)
