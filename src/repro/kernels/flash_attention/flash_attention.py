"""Pallas TPU kernel: online-softmax (flash) causal attention with GQA and
optional sliding window.

Tiling: grid = (B*H, Sq/block_q, Sk/block_k); q/k/v blocks live in VMEM,
running max/denominator/accumulator in VMEM scratch. GQA is handled in the
BlockSpec index map (query head h reads kv head h // group), so grouped KV
is never materialized. The kv axis is the innermost ("arbitrary") grid
dimension; out-of-window blocks are masked.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(scale, window, block_q, block_k, n_k,
                  q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                      # (bq, hd)
    k = k_ref[0].astype(jnp.float32)                      # (bk, hd)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    q_pos = pl.program_id(1) * block_q + \
        jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = j * block_k + \
        jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = k_pos <= q_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                                   # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(j == n_k - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("n_q_heads", "window", "block_q", "block_k",
                              "interpret"))
def flash_attention_pallas(q, k, v, n_q_heads: int, window=None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = True):
    """q: (B*H, Sq, hd); k, v: (B*Hkv, Sk, hd) -> (B*H, Sq, hd).

    Causal attention (positions are absolute indices 0..S-1 on both sides).
    """
    BH, Sq, hd = q.shape
    BHkv, Sk, _ = k.shape
    H = n_q_heads
    Hkv = BHkv // (BH // H)
    G = H // Hkv
    scale = hd ** -0.5

    hp = max(128, -(-hd // 128) * 128)
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    Sqp = -(-Sq // bq) * bq
    Skp = -(-Sk // bk) * bk
    qp = jnp.pad(q, ((0, 0), (0, Sqp - Sq), (0, hp - hd)))
    kp = jnp.pad(k, ((0, 0), (0, Skp - Sk), (0, hp - hd)))
    vp = jnp.pad(v, ((0, 0), (0, Skp - Sk), (0, hp - hd)))
    n_k = Skp // bk

    def kv_head(bh):
        return (bh // H) * Hkv + (bh % H) // G

    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale, window, bq, bk, n_k),
        grid=(BH, Sqp // bq, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, hp), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bk, hp), lambda bh, i, j: (kv_head(bh), j, 0)),
            pl.BlockSpec((1, bk, hp), lambda bh, i, j: (kv_head(bh), j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hp), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sqp, hp), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hp), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(qp, kp, vp)
    return out[:, :Sq, :hd]
