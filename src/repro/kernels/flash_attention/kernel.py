"""Causal flash-attention Pallas TPU kernel (online softmax, GQA-aware).

The dense archs' train/prefill hot path.  Grid (B*H, Sq/bq, Sk/bk) with the
KV axis innermost-sequential; running max/denominator/accumulator live in
VMEM scratch.  GQA is handled in the index map: query-head row bh reads KV
row  (bh // H)*KV + (bh % H) // G  — no materialized K/V repeat (the repeat
is free in addressing, exactly what the MXU wants).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            nk: int, bq: int, bk: int, scale: float, causal: bool):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                          # (bq, hd)
    k = k_ref[0]                                          # (bk, hd)
    v = v_ref[0]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(qpos >= kpos, s, NEG_INF)

    m_prev, l_prev, acc = m_ref[...], l_ref[...], acc_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)                                # (bq, bk)
    corr = jnp.exp(m_prev - m_new)                        # (bq, 1)
    l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_new = acc * corr + jnp.dot(p.astype(v.dtype), v,
                                   preferred_element_type=jnp.float32)
    m_ref[...], l_ref[...], acc_ref[...] = m_new, l_new, acc_new

    @pl.when(ik == nk - 1)
    def _emit():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, kv_heads: int, causal: bool = True,
                           bq: int = 512, bk: int = 512,
                           interpret: bool = False):
    """q (BH, Sq, hd); k,v (BKV, Sk, hd) with BH = B*H, BKV = B*KV."""
    BH, Sq, hd = q.shape
    BKV, Sk, _ = k.shape
    B = BKV // kv_heads
    H = BH // B
    G = H // kv_heads
    bq, bk = min(bq, Sq), min(bk, Sk)
    assert Sq % bq == 0 and Sk % bk == 0
    nq, nk = Sq // bq, Sk // bk
    scale = hd ** -0.5

    def kv_row(bh):
        return (bh // H) * kv_heads + (bh % H) // G

    try:
        cp = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    except Exception:
        cp = None

    return pl.pallas_call(
        functools.partial(_kernel, nk=nk, bq=bq, bk=bk, scale=scale,
                          causal=causal),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bk, hd), lambda bh, iq, ik: (kv_row(bh), ik, 0)),
            pl.BlockSpec((1, bk, hd), lambda bh, iq, ik: (kv_row(bh), ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, hd), jnp.float32)],
        compiler_params=cp,
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
