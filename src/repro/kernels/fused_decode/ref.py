"""Pure-jnp oracles for the fused cohort-decode kernels.

Each oracle IS the composed path the kernel replaces — the dequantize ->
einsum chains of models/attention and models/mlp, and the engine's
``.at[...].set(mode="drop")`` paged scatter — so "fused == ref" means the
fused step is bit-identical to what ``ServingEngine._cohort_fn`` computes
today with three separate dispatches (gather -> lm_decode_step -> scatter).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.quantize import QTensor, dequantize
from repro.models import model as M


def _dq(w):
    return dequantize(w) if isinstance(w, QTensor) else w


def ref_fused_qkv(h, wq, wk, wv,
                  bq: Optional[jnp.ndarray] = None,
                  bk: Optional[jnp.ndarray] = None,
                  bv: Optional[jnp.ndarray] = None):
    """The composed projection: dequantize (XLA-fused) then qkv_proj."""
    from repro.models import attention as attn
    p = {"wq": _dq(wq), "wk": _dq(wk), "wv": _dq(wv)}
    if bq is not None:
        p.update(bq=bq, bk=bk, bv=bv)
    return attn.qkv_proj(p, h)


def ref_fused_mlp(h, w_up, w_down, w_gate=None, *, act: str):
    """The composed FFN: dequantize then models/mlp.apply_mlp."""
    from repro.models import mlp as mlp_mod

    class _Cfg:
        pass

    cfg = _Cfg()
    cfg.act = act
    p = {"w_up": _dq(w_up), "w_down": _dq(w_down)}
    if w_gate is not None:
        p["w_gate"] = _dq(w_gate)
    return mlp_mod.apply_mlp(p, cfg, h)


def paged_context(leaf, tables, heads):
    """Each row's context from a lane-dense paged leaf (L, n_blocks, bs,
    KV*hd) through its block table (bc, W): (L, bc, W*bs) + ``heads``,
    the composed (KV, hd) view.  Sentinel ids gather zeros."""
    L, _, bs, _ = leaf.shape
    bc, W = tables.shape
    return jnp.take(leaf, tables, axis=1, mode="fill",
                    fill_value=0).reshape((L, bc, W * bs) + tuple(heads))


def ref_kv_scatter(blk, off, k_rows, v_rows, k_pool, v_pool):
    """The engine's paged single-position scatter, all layer groups at
    once: rows (L, bc, KV, hd) land as (L, bc, KV*hd) lanes; sentinel
    block ids (== n_blocks) fall out of range and drop."""
    L, bc = k_rows.shape[:2]
    k_pool = k_pool.at[:, blk, off].set(
        k_rows.reshape(L, bc, -1).astype(k_pool.dtype), mode="drop")
    v_pool = v_pool.at[:, blk, off].set(
        v_rows.reshape(L, bc, -1).astype(v_pool.dtype), mode="drop")
    return k_pool, v_pool


def ref_cohort_step(params, cfg, tokens, lengths, slot_ids, tables, pool, *,
                    block_size: int, paged):
    """The composed cohort step, three dispatches (the body
    ServingEngine compiles when the step is not fused): gather each row's
    context from the paged pool, run ONE ``lm_decode_step`` over the
    cohort, scatter the new K/V position back through the block tables.
    This is the oracle the fused step must match."""
    bc = tokens.shape[0]
    bs = block_size
    heads = (cfg.n_kv_heads, cfg.hd)
    layers = []
    for pos, is_paged in enumerate(paged):
        if is_paged:
            layers.append(jax.tree.map(
                lambda l: paged_context(l, tables, heads), pool[pos]))
        else:
            layers.append(jax.tree.map(
                lambda l: jnp.take(l, slot_ids, axis=1,
                                   mode="fill", fill_value=0),
                pool[pos]))
    cache = {"layers": tuple(layers), "index": lengths}
    logits, new = M.lm_decode_step(params, cfg, tokens, cache)
    blk = jnp.take_along_axis(
        tables, (lengths // bs)[:, None], axis=1)[:, 0]
    off = lengths % bs
    out = []
    for pos, is_paged in enumerate(paged):
        if is_paged:
            def scat(l, nl):
                idx = lengths.reshape((1, bc) + (1,) * (nl.ndim - 2))
                row = jnp.take_along_axis(nl, idx, axis=2)
                return l.at[:, blk, off].set(
                    row.reshape(l.shape[0], bc, -1).astype(l.dtype),
                    mode="drop")
            out.append(jax.tree.map(scat, pool[pos], new["layers"][pos]))
        else:
            out.append(jax.tree.map(
                lambda l, nl: l.at[:, slot_ids].set(
                    nl.astype(l.dtype), mode="drop"),
                pool[pos], new["layers"][pos]))
    return logits, tuple(out)
