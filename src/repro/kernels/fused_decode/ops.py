"""Public wrappers for the fused low-bit cohort-decode step.

``cohort_step`` is the engine-facing entry: the batched decode inner loop
``ServingEngine._cohort_fn`` compiles per cohort-size bucket.  With
``use_fused=False`` it runs the composed oracle (ref.py — today's three
dispatches: gather, ``lm_decode_step``, scatter).  With ``use_fused=True``
each layer group runs

* :func:`fused_qkv` — one Pallas pass unpacking the packed q4/q8 weights
  in VMEM and computing the three QKV GEMMs (the fp16 weight matrix never
  materializes to HBM);
* the *composed* attention core (``attention.attn_context``) and output
  projection — softmax math is shared code with the oracle, so the paths
  cannot drift;
* :func:`kv_scatter` — the paged single-position K/V write, aliased in
  place, sentinel rows writing nothing (replaces the oracle's whole-pool
  ``.at[...].set`` pass);
* :func:`fused_mlp` — unpack + gate/up/act/down in one pass.

``interpret=`` resolves through kernels/dispatch *outside* the engine's
jit (the engine resolves at ``_cohort_fn`` build time and passes the
resolved flag in), so ``force_ref()`` / ``REPRO_FORCE_REF`` behave like
every other kernel wrapper.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.quantize import QTensor, dequantize
from repro.kernels.dispatch import resolve_interpret
from repro.kernels.fused_decode import kernel as K
from repro.kernels.fused_decode.ref import (paged_context, ref_cohort_step,
                                            ref_fused_mlp, ref_fused_qkv,
                                            ref_kv_scatter)


def fused_qkv(h, wq, wk, wv, bq=None, bk=None, bv=None, *,
              use_kernel: Optional[bool] = None,
              interpret: Optional[bool] = None):
    """h (bc,1,D) -> (q, k, v); weights dense arrays or packed QTensors.

    ``interpret`` resolves through kernels/dispatch."""
    if use_kernel is not None and not use_kernel:
        return ref_fused_qkv(h, wq, wk, wv, bq, bk, bv)
    return K.fused_qkv_pallas(h, wq, wk, wv, bq, bk, bv,
                              interpret=resolve_interpret(interpret))


def fused_mlp(h, w_up, w_down, w_gate=None, *, act: str,
              use_kernel: Optional[bool] = None,
              interpret: Optional[bool] = None):
    """h (bc,1,D) -> (bc,1,D); the sublayer FFN in one fused pass.

    ``interpret`` resolves through kernels/dispatch."""
    if use_kernel is not None and not use_kernel:
        return ref_fused_mlp(h, w_up, w_down, w_gate, act=act)
    return K.fused_mlp_pallas(h, w_up, w_down, w_gate, act=act,
                              interpret=resolve_interpret(interpret))


def kv_scatter(blk, off, k_rows, v_rows, k_pool, v_pool, *,
               use_kernel: Optional[bool] = None,
               interpret: Optional[bool] = None):
    """Write each cohort row's new K/V position (all layer groups at once)
    into the lane-dense paged pool: rows (L, bc, KV, hd), pools (L,
    n_blocks, bs, KV*hd).

    Pools are donated (aliased); sentinel rows write nothing.
    ``interpret`` resolves through kernels/dispatch."""
    if use_kernel is not None and not use_kernel:
        return ref_kv_scatter(blk, off, k_rows, v_rows, k_pool, v_pool)
    return K.kv_row_scatter_pallas(
        jnp.asarray(blk, jnp.int32), jnp.asarray(off, jnp.int32),
        k_rows, v_rows, k_pool, v_pool,
        interpret=resolve_interpret(interpret))


def fused_supported(cfg) -> bool:
    """The fused path covers the uniform dense-attention serving archs
    (every group position paged: softmax attention + dense MLP).  Hybrid
    SSM groups, MoE FFNs, and linear attention keep the composed path."""
    from repro.models import decoder as dec
    if dec.group_size(cfg) != 1 or cfg.family == "ssm":
        return False
    if dec.cfg_attn_impl(cfg) == "linear" or cfg.moe is not None:
        return False
    return cfg.d_ff > 0


def _dq(w):
    return dequantize(w) if isinstance(w, QTensor) else w


def _fused_cohort_step(params, cfg, tokens, lengths, slot_ids, tables,
                       pool, *, block_size: int, interpret: bool):
    """The fused replacement for ref_cohort_step.

    Structure matters for bit-exactness: the composed path runs the layer
    groups through ``lax.scan`` (decoder.stack_decode), and on CPU XLA
    compiles a scan body differently from an unrolled Python loop — the
    bf16 GEMM accumulation order changes and logits drift ~1e-2.  So the
    fused path is the *same* scan: one ``lax.scan`` over the stacked group
    params whose body swaps the dequant->einsum chains for the fused
    Pallas kernels (interpret-mode pallas inside a scan body is bit-equal
    to the jnp ops it replaces — verified property, see
    tests/test_fused_decode.py).  Everything the kernels do not fuse —
    embed, norms, rope, the attention softmax/context, the output
    projection, the LM head — is the same shared code the composed path
    runs, so equality with the oracle reduces to the per-kernel
    contracts.  The new K/V rows come out of the scan stacked and hit the
    pool in ONE aliased scatter kernel (grid (layer tiles, bc)) instead
    of the composed path's whole-pool gather-update-rescatter."""
    from repro.distributed.sharding import constrain_residual
    from repro.models import attention as attn
    from repro.models import model as M
    from repro.models.common import apply_norm

    del slot_ids                       # every position is paged (supported
    #                                    archs have no slot-state layers)
    bs = block_size
    k_pool, v_pool = pool[0]

    index = jnp.asarray(lengths)
    positions = index[:, None].astype(jnp.int32)
    mrope = jnp.stack([positions] * 3) if cfg.rope == "mrope" else None
    rope_fn = M.make_rope_fn(cfg, positions, mrope)

    x = M._embed(params, cfg, tokens)
    # cohort context gather — identical to the composed path (the fused
    # kernels replace the *scatter* side; reads stay one gather on the
    # lane-dense pool, and only the gathered context takes (KV, hd))
    heads = (cfg.n_kv_heads, cfg.hd)
    gk = paged_context(k_pool, tables, heads)
    gv = paged_context(v_pool, tables, heads)
    blk = jnp.take_along_axis(tables, (lengths // bs)[:, None], axis=1)[:, 0]
    off = lengths % bs

    def body(x, xs):
        gp, (ck, cv) = xs
        sub = gp[0]                    # fused_supported => group_size == 1
        mix = sub["mixer"]
        h = apply_norm(sub["norm1"], x)
        q, k_new, v_new = fused_qkv(
            h, mix["wq"], mix["wk"], mix["wv"],
            *(_dq(mix.get(b)) for b in ("bq", "bk", "bv")),
            interpret=interpret)
        q, k_new = rope_fn(q), rope_fn(k_new)
        o = attn.attn_context(q, k_new, v_new, ck, cv, index, cfg)
        y = attn.out_proj({"wo": _dq(mix["wo"])}, o)
        x = x + y
        h2 = apply_norm(sub["norm2"], x)
        y2 = fused_mlp(h2, sub["ffn"]["w_up"], sub["ffn"]["w_down"],
                       sub["ffn"].get("w_gate"), act=cfg.act,
                       interpret=interpret)
        x = x + constrain_residual(y2)
        return x, (k_new[:, 0], v_new[:, 0])

    x, (k_rows, v_rows) = jax.lax.scan(
        body, x, (params["layers"], (gk, gv)))
    k_pool, v_pool = kv_scatter(blk, off, k_rows, v_rows, k_pool, v_pool,
                                interpret=interpret)

    logits = M._head(params, cfg, x)
    return logits[:, 0], ((k_pool, v_pool),)


def cohort_step(params, cfg, tokens, lengths, slot_ids, tables, pool, *,
                block_size: int, paged,
                use_fused: Optional[bool] = None,
                interpret: Optional[bool] = None):
    """One batched cohort decode step against the paged pool.

    tokens (bc,1) int32; lengths/slot_ids (bc,) int32; tables (bc, W);
    pool: tuple of per-position cache trees (donated).  Returns
    (logits (bc, V), new pool).  ``use_fused=None`` resolves to whether
    the arch is fused-supported; ``interpret`` resolves through
    kernels/dispatch."""
    if use_fused is None:
        use_fused = fused_supported(cfg)
    if not use_fused:
        return ref_cohort_step(params, cfg, tokens, lengths, slot_ids,
                               tables, pool, block_size=block_size,
                               paged=paged)
    assert fused_supported(cfg), (
        "use_fused=True needs a uniform dense-attention arch "
        f"(family={cfg.family}, attn_impl={cfg.attn_impl})")
    assert all(paged), "fused cohort step expects every position paged"
    return _fused_cohort_step(params, cfg, tokens, lengths, slot_ids,
                              tables, pool, block_size=block_size,
                              interpret=resolve_interpret(interpret))
