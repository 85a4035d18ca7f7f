"""Edge scoring (paper Eq. 1 + virtual-loss variants), backend-generic.

One scoring routine shared — verbatim — by the sequential numpy oracle,
the batched jit ops and the Pallas kernel reference.  All inputs are
integers (counts + Qm.16 sums); all transcendental inputs come from the
shared ln-table; every float op used (convert / divide / sqrt / add /
multiply / round) is IEEE-754 correctly rounded, so numpy-f32 and jax-f32
produce bit-identical scores and therefore identical argmax decisions.
This is how the paper's "exact same outputs as a CPU-only system" claim
survives vectorization.

Two backends need help for that.  A TPU's float32 divide and sqrt may
miss the nearest float by an ulp, so off numpy `div_rn` / `sqrt_rn`
correct the backend's result to the nearest one from exact products
(Dekker: float32 multiplies and adds only).  And XLA may reassociate a
product with a broadcast scalar (``(c * P) * sqrt_ns`` into
``P * (c * sqrt_ns)``), or fuse a product into the add after it (one
rounding for two); the PUCT numerator and the UCT bonus pass through a
select, which neither rewrite crosses.  One such miss, on an edge whose
score ties another's to the Qm.16 unit, changes an argmax: a different
tree.

Shapes: edge inputs are ``[..., Fp]``; node inputs broadcast as ``[..., 1]``.
Returns int32 fixed-point scores ``[..., Fp]`` where invalid lanes are
FX_NEG_INF and never-visited edges are FX_FORCE_EXPLORE (uct) so they win
any comparison against real scores (<= FX_MAX).
"""

from __future__ import annotations

import numpy as np

from repro.core import fixedpoint as fx
from repro.core.tree import NULL, TreeConfig

# Veltkamp's split of a float32 into two halves of 12 significant bits
_SPLIT = 4097.0


def _exact_product(a, b, xp):
    """(p, e) with p = fl(a * b) and p + e = a * b exactly (Dekker), for
    products that neither overflow nor underflow."""
    f32 = xp.float32

    def split(x):
        c = f32(_SPLIT) * x
        hi = c - (c - x)
        return hi, x - hi

    p = a * b
    ah, al = split(a)
    bh, bl = split(b)
    return p, ((ah * bh - p) + ah * bl + al * bh) + al * bl


# A backend's divide or sqrt lands within two ulps of the nearest float;
# each round moves one ulp toward it.
_ROUNDS = 2


def nearest_quotient(a, b, q, xp):
    """The float32 nearest a / b, from q within two ulps of it.  a - q*b
    is exact (a - p by Sterbenz, the error e from Dekker), and so is the
    step d of one ulp; the quotient passes the midpoint q + d/2 exactly
    when |a - q*b| > |d*b| / 2, never equal (no quotient of floats is a
    midpoint)."""
    f32 = xp.float32
    for _ in range(_ROUNDS):
        p, e = _exact_product(q, b, xp)
        r = (a - p) - e
        up = (r > 0) == (b > 0)
        q2 = xp.nextafter(q, xp.where(up, f32(np.inf), f32(-np.inf)))
        past = xp.abs(r) - xp.abs((q2 - q) * b) * f32(0.5) > 0
        q = xp.where((r != 0) & past, q2, q)
    return q


def nearest_root(x, s, xp):
    """The float32 nearest sqrt(x), from s within two ulps of it.  With
    r = x - s*s exact and d the ulp step toward the root, the root passes
    the midpoint m = s + d/2 exactly when x - m*m = (r - s*d) - d*d/4 has
    the sign of d; r - s*d is split into its rounded value and error
    (Knuth's two-sum), so that sign is exact."""
    f32 = xp.float32
    for _ in range(_ROUNDS):
        p, e = _exact_product(s, s, xp)
        r = (x - p) - e
        s2 = xp.nextafter(s, xp.where(r > 0, f32(np.inf), f32(-np.inf)))
        d = s2 - s
        sd = s * d
        y = r - sd
        yv = y - r
        y_err = (r - (y - yv)) - (sd + yv)
        z = y + (y_err - d * d * f32(0.25))
        q = (r != 0) & (z != 0) & ((z > 0) == (d > 0))
        s = xp.where(q, s2, s)
    return s


def div_rn(a, b, xp=np):
    """a / b in float32, rounded to nearest on every backend."""
    if xp is np:
        return a / b
    return nearest_quotient(a, b, a / b, xp)


def sqrt_rn(x, xp=np):
    """sqrt(x) in float32, rounded to nearest on every backend."""
    if xp is np:
        return np.sqrt(x)
    return nearest_root(x, xp.sqrt(x), xp)


def edge_scores_fx(
    cfg: TreeConfig,
    *,
    child,        # [..., Fp] i32
    edge_N,       # [..., Fp] i32
    edge_W,       # [..., Fp] i32 (Qm.16)
    edge_VL,      # [..., Fp] i32
    edge_P,       # [..., Fp] i32 (Qm.16)
    node_N,       # [..., 1]  i32
    node_O,       # [..., 1]  i32
    num_actions,  # [..., 1]  i32
    log_table=None,  # [2X+4] f32 (omit iff log_ns given)
    xp=np,
    lane=None,       # optional precomputed lane-index array [..., Fp]
                     # (Pallas kernels pass a 2-D broadcasted iota: 1-D iota
                     #  does not lower on TPU)
    log_ns=None,     # optional precomputed ln(ns) [..., 1] f32 (kernels do
                     #  the scalar table load themselves)
):
    i32, f32 = xp.int32, xp.float32
    Fp = child.shape[-1]
    if lane is None:
        lane = xp.arange(Fp, dtype=i32)
    valid = (lane < num_actions) & (child != NULL)

    if cfg.vl_mode == "wu":
        ne = edge_N + edge_VL                    # N̄ = N + O (in-flight)
        ns = node_N + node_O
    else:
        ne = edge_N
        ns = node_N
    ns = xp.minimum(ns, i32(2 * cfg.X + 3))      # log-table bound (tree.py)

    ne_safe = xp.maximum(ne, i32(1)).astype(f32)
    if log_ns is None:
        log_ns = xp.take(log_table, ns, axis=0)  # [..., 1] f32 (shared table)

    if cfg.score_fn == "uct":
        q = div_rn(edge_W.astype(f32) * f32(fx.FX_INV_SCALE), ne_safe, xp)
        u = xp.where(valid, f32(cfg.beta)
                     * sqrt_rn(div_rn(log_ns, ne_safe, xp), xp), f32(0.0))
        base = fx.encode(q + u, xp=xp)
        base = xp.where(ne == 0, fx.FX_FORCE_EXPLORE, base)
    else:  # puct: Q + c * P * sqrt(Ns) / (1 + Ne); Q := 0 when unvisited
        q = div_rn(edge_W.astype(f32) * f32(fx.FX_INV_SCALE), ne_safe, xp)
        q = xp.where(ne == 0, f32(0.0), q)
        sqrt_ns = sqrt_rn(ns.astype(f32), xp)
        p_f = edge_P.astype(f32) * f32(fx.FX_INV_SCALE)
        cp = xp.where(valid, f32(cfg.beta) * p_f, f32(0.0))
        u = div_rn(cp * sqrt_ns, f32(1.0) + ne.astype(f32), xp)
        base = fx.encode(q + u, xp=xp)

    if cfg.vl_mode == "constant":
        # Paper Alg. 1 line 5: uct(s, s_hat) -= VL, applied per in-flight
        # worker; exact integer arithmetic in the Qm.16 domain.
        base = base - i32(cfg.vl_const_fx) * edge_VL

    return xp.where(valid, base, fx.FX_NEG_INF)


def argmax_first(scores_fx, xp=np):
    """First-maximum argmax over the last axis (deterministic tie-break,
    matching both np.argmax and jnp.argmax semantics)."""
    return xp.argmax(scores_fx, axis=-1).astype(xp.int32)


def is_leaf(cfg: TreeConfig, *, num_expanded, num_actions, terminal, depth, xp=np):
    """Selection-leaf predicate (paper §II-A; see TreeConfig.leaf_mode)."""
    if cfg.leaf_mode == "partial":
        open_node = num_expanded < num_actions
    else:
        open_node = num_expanded == 0
    return open_node | (terminal != 0) | (depth >= cfg.D) | (num_actions == 0)
