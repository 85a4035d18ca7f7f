"""Pallas TPU kernel: flash attention (LM simulation-backend hot-spot).

The paper's Gomoku benchmark replaces software simulation with DNN
inference; in this framework the simulation backend generalizes to LM
`serve_step`, whose prefill is MXU-bound attention.  This kernel is the
TPU-optimized path for that hot-spot: classic FlashAttention-2 blocking
with explicit BlockSpec VMEM tiles, online softmax, causal and
sliding-window masking, GQA via grid-mapped KV heads.

Grid: (batch, q_heads, q_blocks); each program streams KV blocks for one
query tile.  Block shapes default to (128, head_dim) tiles — MXU-aligned
(multiples of 128 on the contracting/lane dims for f32/bf16).

ref.py oracle: repro.models.attention.naive_attention (same math, jnp).
Validated with interpret=True across shape/dtype/mask sweeps in
tests/test_flash_kernel.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.common import canonical_index

NEG = -2.0e38


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, causal, window, scale,
                  blk_q, blk_k, seq_k, seq_k_real):
    qi = pl.program_id(2)
    q = q_ref[0, :, :].astype(jnp.float32) * scale          # [blk_q, dh]
    nk = seq_k // blk_k

    m0 = jnp.full((blk_q, 1), NEG, jnp.float32)
    l0 = jnp.zeros((blk_q, 1), jnp.float32)
    a0 = jnp.zeros((blk_q, q.shape[-1]), jnp.float32)

    qpos = qi * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)

    def body(kj, carry):
        m_run, l_run, acc = carry
        # leading axis indexed with a length-1 dslice, not a bare int: the
        # interpreter's load-discharge rule rejects scalar ints in a mixed
        # index tuple (every 16-case sweep in tests/test_flash_kernel.py
        # crashed on it; kernel numerics were never the problem).  Starts
        # go through canonical_index so the tuple stays one dtype under
        # JAX_ENABLE_X64.
        kstart = canonical_index(kj * blk_k)
        k = k_ref[pl.ds(canonical_index(0), 1), pl.ds(kstart, blk_k), :][0]
        v = v_ref[pl.ds(canonical_index(0), 1), pl.ds(kstart, blk_k), :][0]
        s = q @ k.astype(jnp.float32).T                      # [blk_q, blk_k]
        kpos = kj * blk_k + jax.lax.broadcasted_iota(
            jnp.int32, (blk_q, blk_k), 1)
        msk = jnp.ones((blk_q, blk_k), jnp.bool_)
        if causal:
            msk &= kpos <= qpos
        if window is not None:
            msk &= kpos > qpos - window
        msk &= kpos < seq_k_real            # drop padded keys
        s = jnp.where(msk, s, NEG)
        m_new = jnp.maximum(m_run, jnp.max(s, -1, keepdims=True))
        alpha = jnp.exp(m_run - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_run * alpha + jnp.sum(p, -1, keepdims=True)
        acc = acc * alpha + p @ v.astype(jnp.float32)
        return m_new, l_new, acc

    # causal: stop at the diagonal block; window: also skip blocks fully
    # left of the window.
    hi = jnp.minimum(nk, qi * blk_q // blk_k + 1) if causal else nk
    lo = 0
    if window is not None:
        lo = jnp.maximum(0, (qi * blk_q - window) // blk_k)
    m, l, acc = jax.lax.fori_loop(lo, hi, body, (m0, l0, a0))
    o_ref[0, :, :] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "blk_q", "blk_k", "interpret"))
def flash_attention(q, k, v, *, interpret: bool, causal=True, window=None,
                    blk_q=128, blk_k=128):
    """q: [B, Sq, H, dh]; k/v: [B, Sk, Hkv, dh] (H % Hkv == 0).
    Returns [B, Sq, H, dh].  Sq/Sk padded to block multiples internally."""
    B, Sq, H, dh = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    g = H // Hkv
    # weak python float, not an np.float64 scalar: a strong f64 scale
    # would widen the whole online-softmax carry under JAX_ENABLE_X64
    scale = float(1.0 / np.sqrt(dh))

    nq = -(-Sq // blk_q)
    nk = -(-Sk // blk_k)
    Sqp, Skp = nq * blk_q, nk * blk_k
    qp = jnp.pad(q, ((0, 0), (0, Sqp - Sq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, Skp - Sk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, Skp - Sk), (0, 0), (0, 0)))
    # pad keys beyond Sk are masked by causality for Sq<=Sk; for safety add
    # an explicit window-independent validity via causal/window masks only
    # when Skp == Sk; otherwise rely on qpos<=Sq padding being discarded.
    qh = qp.transpose(0, 2, 1, 3)        # [B, H, Sqp, dh]
    kh = kp.transpose(0, 2, 1, 3)        # [B, Hkv, Skp, dh]
    vh = vp.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _flash_kernel, causal=causal, window=window, scale=scale,
        blk_q=blk_q, blk_k=blk_k, seq_k=Skp, seq_k_real=Sk)
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq),
        in_specs=[
            pl.BlockSpec((1, blk_q, dh), lambda b, h, i: (b * H + h, i, 0)),
            pl.BlockSpec((1, Skp, dh), lambda b, h, i: (b * Hkv + h // g, 0, 0)),
            pl.BlockSpec((1, Skp, dh), lambda b, h, i: (b * Hkv + h // g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk_q, dh), lambda b, h, i: (b * H + h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sqp, dh), q.dtype),
        interpret=interpret,
    )(qh.reshape(B * H, Sqp, dh), kh.reshape(B * Hkv, Skp, dh),
      vh.reshape(B * Hkv, Skp, dh))
    out = out.reshape(B, H, Sqp, dh).transpose(0, 2, 1, 3)
    return out[:, :Sq]
