"""Fused W{2,4,8}A16 dequant-GEMM Pallas TPU kernel.

The paper's OpenCL kernel "unpacks and rescales int4 weights in-register
within the GEMM loop, followed immediately by FP16 FMAs ... eliminates
intermediate buffers and memory passes" (§3.2 GPU).  TPU adaptation:

* weights live in HBM as int32 words (32/bits codes each) + per-group
  scales — the *storage* format is the paper's; the compute unit is the MXU,
  so "FP16 FMAs" become bf16 MXU matmuls with fp32 accumulators;
* each grid step stages one (bn x bk) packed tile into VMEM, unpacks with
  vector shifts/masks, rescales from a VMEM-resident scale tile (the analogue
  of the paper's LDS scale tables), and feeds the MXU directly — the
  unpacked weight tile never round-trips to HBM;
* the epilogue (bias + activation) is fused into the last K step, exactly
  like the paper's "epilogue that can fuse bias and activation".

Grid: (M/bm, N/bn, K/bk), K innermost (sequential accumulation into a VMEM
scratch accumulator).  Tiles are MXU-aligned (multiples of 128 on M/N).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dequant_gemm.ref import ACTS


def _unpack_tile(codes, bits: int):
    """(bn, bkw) int32 words -> (bn, bkw*per_word) signed int32 codes."""
    pw = 32 // bits
    shifts = jax.lax.broadcasted_iota(jnp.int32, (1, 1, pw), 2) * bits
    field = jax.lax.shift_right_logical(codes[:, :, None], shifts)
    field = jax.lax.bitwise_and(field, (1 << bits) - 1)
    sign = 1 << (bits - 1)
    q = jnp.where(field >= sign, field - (1 << bits), field)
    bn, bkw, _ = q.shape
    return q.reshape(bn, bkw * pw)


def _expand_scales(scales, group_size: int):
    """(bn, bk//G) -> (bn, bk) by broadcast (no gather)."""
    bn, ng = scales.shape
    s = jnp.broadcast_to(scales[:, :, None], (bn, ng, group_size))
    return s.reshape(bn, ng * group_size)


def _body(x_ref, codes_ref, scales_ref, bias_ref, out_ref, acc_ref, *,
          bits: int, group_size: int, nk: int, act: Optional[str]):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = _unpack_tile(codes_ref[...], bits)                  # (bn, bk) int32
    s = _expand_scales(scales_ref[...].astype(jnp.float32), group_size)
    w = (q.astype(jnp.float32) * s).astype(x_ref.dtype)     # in-register tile
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                 # MXU, fp32 acc

    @pl.when(k == nk - 1)
    def _epilogue():
        r = acc_ref[...]
        if bias_ref is not None:
            r = r + bias_ref[...].astype(jnp.float32)
        out_ref[...] = ACTS[act](r).astype(out_ref.dtype)


def _kernel_bias(x_ref, codes_ref, scales_ref, bias_ref, out_ref, acc_ref,
                 **kw):
    _body(x_ref, codes_ref, scales_ref, bias_ref, out_ref, acc_ref, **kw)


def _kernel_nobias(x_ref, codes_ref, scales_ref, out_ref, acc_ref, **kw):
    _body(x_ref, codes_ref, scales_ref, None, out_ref, acc_ref, **kw)


def dequant_gemm_pallas(x, codes, scales, bias=None, *, bits: int,
                        group_size: int, act: Optional[str] = None,
                        bm: int = 128, bn: int = 128, bk: int = 512,
                        interpret: bool = False):
    """x (M, K) @ W(N, K).T with W packed as codes (N, K*bits/32) int32 and
    scales (N, K//group_size).  Returns (M, N) in x.dtype."""
    M, K = x.shape
    N = scales.shape[0]
    pw = 32 // bits
    assert K % bk == 0 and bk % group_size == 0 and bk % pw == 0
    assert M % bm == 0 and N % bn == 0, (M, bm, N, bn)
    nk = K // bk

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),        # x tile
        pl.BlockSpec((bn, bk // pw), lambda i, j, k: (j, k)),  # packed words
        pl.BlockSpec((bn, bk // group_size), lambda i, j, k: (j, k)),
    ]
    args = [x, codes, scales]
    kern = _kernel_nobias
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, k: (0, j)))
        args.append(bias.reshape(1, N))
        kern = _kernel_bias

    try:
        cp = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    except Exception:
        cp = None

    return pl.pallas_call(
        functools.partial(kern, bits=bits, group_size=group_size, nk=nk,
                          act=act),
        grid=(M // bm, N // bn, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=cp,
        interpret=interpret,
        name="dequant_gemm",
    )(*args)
