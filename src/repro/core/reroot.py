"""Subtree-reusing Tree Flush (beyond-paper).

The paper flushes the entire tree at each MCTS step ("the best child
becomes the new root while the rest of the tree are flushed") because the
FPGA statically banks SRAM per level — its own future-work section names
dynamic bank management as an open problem.  On TPU the UCT is just
arrays, so we can re-root: extract the chosen child's subtree, compact
node ids, and keep all of its statistics — every simulation spent below
the chosen action carries over to the next step.

Host-side numpy (runs at the step boundary, off the hot superstep path).
"""

from __future__ import annotations

import numpy as np

from repro.core.tree import NULL, TreeConfig, UCTree


def reroot(cfg: TreeConfig, snap: dict, new_root: int):
    """snap: numpy rows [0, N) of a UCTree's per-node arrays with N at or
    above its size (executor.slot_rows(), or a full snapshot).  Returns
    (the re-rooted tree's N rows with its `size` and `root`, the old ->
    new id map over the N rows, NULL for dropped nodes)."""
    child = snap["child"]
    N = len(child)
    # BFS from new_root, one level at a time: each level is its parents'
    # unseen children in order, first occurrence kept -- a node queue's
    # order, with the scan in numpy
    seen = np.zeros(N, bool)
    seen[new_root] = True
    level = np.array([new_root], np.int64)
    levels = [level]
    while level.size:
        kids = child[level].reshape(-1)
        kids = kids[kids != NULL]
        kids = kids[~seen[kids]]
        _, first = np.unique(kids, return_index=True)
        level = kids[np.sort(first)].astype(np.int64)
        seen[level] = True
        levels.append(level)
    order = np.concatenate(levels)
    n = len(order)
    old2new = np.full(N, NULL, np.int32)
    old2new[order] = np.arange(n, dtype=np.int32)

    out = {}
    for k in ("edge_N", "edge_W", "edge_VL", "edge_P",
              "num_expanded", "num_actions", "terminal",
              "node_N", "node_O"):
        dst = np.zeros_like(snap[k])
        dst[:n] = snap[k][order]
        out[k] = dst
    ch = np.full_like(child, NULL)
    kept = child[order]
    ch[:n] = np.where(kept != NULL, old2new[np.clip(kept, 0, N - 1)], NULL)
    out["child"] = ch
    nd = np.zeros_like(snap["node_depth"])
    nd[:n] = snap["node_depth"][order] - snap["node_depth"][new_root]
    out["node_depth"] = nd
    out["size"] = np.int32(n)
    out["root"] = np.int32(0)
    return out, old2new


def reroot_tree(cfg: TreeConfig, snap: dict, new_root: int, xp):
    arrays, old2new = reroot(cfg, snap, new_root)
    arrays["log_table"] = snap["log_table"]
    t = UCTree(**{k: xp.asarray(v) for k, v in arrays.items()})
    return t, old2new
