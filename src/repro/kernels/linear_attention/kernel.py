"""Streaming linear-attention Pallas TPU kernel (paper §3.2 "GPU").

NANOMIND replaces quadratic attention with a "kernelized, streaming variant
[that] maintains running summaries of past keys and values".  TPU shape:

* grid (B*H, S/C): the chunk axis is sequential; the (hd x hd) running
  summary S and the hd-vector normalizer z live in VMEM scratch and persist
  across chunk steps (reset at c == 0);
* per chunk the MXU computes the intra-chunk causal part as two dense
  (C x hd)(hd x C) matmuls + one (C x C)(C x hd), and the inter-chunk part
  as a single matmul against the running state — "a single matrix pass",
  never materializing the T x T score matrix;
* the final state/z are emitted so decode can continue the stream with the
  paper's single mat-vec per token (see ops.decode_step).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _phi(x):
    return jax.nn.elu(x.astype(jnp.float32)) + 1.0


def _kernel(q_ref, k_ref, v_ref, o_ref, state_out_ref, z_out_ref,
            state_ref, z_ref, *, nc: int, eps: float):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)
        z_ref[...] = jnp.zeros_like(z_ref)

    qf = _phi(q_ref[0])                                   # (C, hd) f32
    kf = _phi(k_ref[0])
    vf = v_ref[0].astype(jnp.float32)
    C = qf.shape[0]

    state, z = state_ref[...], z_ref[...]                 # (hd,hd), (1,hd)
    o_inter = jnp.dot(qf, state, preferred_element_type=jnp.float32)
    z_inter = jnp.dot(qf, z.T, preferred_element_type=jnp.float32)  # (C,1)

    s = jnp.dot(qf, kf.T, preferred_element_type=jnp.float32)       # (C,C)
    ii = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    s = jnp.where(ii >= jj, s, 0.0)                       # causal (incl diag)
    o_intra = jnp.dot(s, vf, preferred_element_type=jnp.float32)
    z_intra = jnp.sum(s, axis=1, keepdims=True)           # (C,1)

    den = jnp.maximum(z_inter + z_intra, eps)
    o_ref[0] = ((o_inter + o_intra) / den).astype(o_ref.dtype)

    state_ref[...] = state + jnp.dot(kf.T, vf,
                                     preferred_element_type=jnp.float32)
    z_ref[...] = z + jnp.sum(kf, axis=0, keepdims=True)

    @pl.when(c == nc - 1)
    def _emit():
        state_out_ref[0] = state_ref[...]
        z_out_ref[0] = z_ref[...]


def linear_attention_pallas(q, k, v, *, chunk: int = 256,
                            eps: float = 1e-6, interpret: bool = False):
    """q,k,v (BH, S, hd) -> (out (BH,S,hd), state (BH,hd,hd), z (BH,1,hd))."""
    BH, S, hd = q.shape
    chunk = min(chunk, S)
    assert S % chunk == 0
    nc = S // chunk

    try:
        cp = pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"))
    except Exception:
        cp = None

    return pl.pallas_call(
        functools.partial(_kernel, nc=nc, eps=eps),
        grid=(BH, nc),
        in_specs=[pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0))] * 3,
        out_specs=[
            pl.BlockSpec((1, chunk, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, hd, hd), lambda b, c: (b, 0, 0)),
            pl.BlockSpec((1, 1, hd), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, hd), q.dtype),
            jax.ShapeDtypeStruct((BH, hd, hd), jnp.float32),
            jax.ShapeDtypeStruct((BH, 1, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32),
                        pltpu.VMEM((1, hd), jnp.float32)],
        compiler_params=cp,
        interpret=interpret,
        name="linear_attention",
    )(q, k, v)
