"""The in-tree work one superstep needs, counted from the shapes.

Per active tree slot and superstep, p workers each select down a path of
at most D edges and back the value up along it.  What the algorithm
must touch, whatever code does it (4-byte words throughout):

  selection, per worker and level
    read   the node's edge rows: child, edge_N, edge_W, edge_VL (and
           edge_P for PUCT): Fp words each
    read   the node's scalars: node_N, node_O, num_expanded,
           num_actions, terminal, and one ln-table entry: 6 words
    write  one edge_VL word and the child's node_O word: 2 words
    ops    the edge score over Fp lanes, SCORE_OPS each, and the
           first-maximum over them, 2 each
  selection, per worker
    write  the root's node_O, the path (2 D words), depth and leaf
  backup, per worker and level
    update edge_N, edge_W, edge_VL, node_N, node_O: 5 words read and 5
           written; 6 integer ops
  backup, per worker
    read   the path (2 D words) and depth, leaf, expansion, simulated
           node and value (5 words); update the leaf's node_N, node_O

The path length is taken at D, so this is an upper bound of what a
superstep needs.  It leaves out on purpose everything an implementation
adds: moving whole arrays in and out of fast memory, repacking layouts.
"""

from __future__ import annotations

WORD = 4
SCORE_OPS = {"uct": 16, "puct": 17}   # per lane: see the comment below
ARGMAX_OPS = 2

# uct: ne add, max, 2 converts, scale, divide (q); divide, sqrt,
#      multiply (u); add, scale, round, 2 clips, convert, zero test,
#      select (encode, unvisited) -> 16
# puct: the same with the unvisited test on q, one more multiply for the
#      prior and no ln-table term -> 17


def lanes(F: int) -> int:
    """Edge lanes per node: F rounded up to a power of two."""
    return 1 << max(0, (F - 1).bit_length())


def per_slot_superstep(p: int, D: int, F: int, puct: bool) -> dict:
    """Operations and bytes one active slot needs in one superstep."""
    Fp = lanes(F)
    edge_rows = 5 if puct else 4
    sel_level_words = edge_rows * Fp + 6 + 2
    sel_worker_words = 1 + 2 * D + 2
    back_level_words = 10
    back_worker_words = 2 * D + 5 + 4
    words = p * (D * (sel_level_words + back_level_words)
                 + sel_worker_words + back_worker_words)
    score = SCORE_OPS["puct" if puct else "uct"]
    ops = p * D * (Fp * (score + ARGMAX_OPS) + 6)
    return {"ops": ops, "bytes": words * WORD}


def least_seconds(work: dict, peaks: dict) -> tuple[float, str]:
    """The least time the chip needs for `work`, and what bounds it."""
    t_ops = work["ops"] / peaks["flops_per_s"]
    t_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "ops")
