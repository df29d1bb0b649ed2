"""Mamba-2 SSD (state-space dual) chunked-scan Pallas TPU kernel.

The SSD dual form is exactly the structure the paper's streaming linear
attention uses (intra-chunk quadratic + inter-chunk state passing), with a
data-dependent decay: the MXU sees three dense matmuls per chunk
(C.B^T, w.x, C.h) while the (P x N) state is carried in VMEM scratch across
the sequential chunk axis.

Grid: (B, H, S/C).  Per-head blocks keep the working set tiny:
x (C,P), B/C (C,N), dt (C,), state (P,N) — ~200 KB of VMEM at the
assigned-arch sizes (C=256, P=64..128, N=128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, hout_ref, h_ref, *,
            nc: int):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    xb = x_ref[0, 0].astype(jnp.float32)                  # (C, P)
    dt = dt_ref[0, 0].astype(jnp.float32)                 # (C,)
    Bb = b_ref[0, 0].astype(jnp.float32)                  # (C, N)
    Cb = c_ref[0, 0].astype(jnp.float32)                  # (C, N)
    A = a_ref[0, 0]                                       # scalar

    la = dt * A                                           # (C,) log-decay
    cum = jnp.cumsum(la)                                  # (C,)
    C_len = cum.shape[0]

    # intra-chunk: w[i,j] = (C_i . B_j) exp(cum_i - cum_j) dt_j, j <= i
    # (mask inside the exp: the j > i arguments are large-positive and
    # would overflow — same hazard as the jnp oracle's VJP)
    ii = jax.lax.broadcasted_iota(jnp.int32, (C_len, C_len), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (C_len, C_len), 1)
    dec = jnp.exp(jnp.where(ii >= jj, cum[:, None] - cum[None, :], -1e30))
    cb = jnp.dot(Cb, Bb.T, preferred_element_type=jnp.float32)
    w = cb * dec * dt[None, :]
    y_intra = jnp.dot(w, xb, preferred_element_type=jnp.float32)

    # inter-chunk: y_i += exp(cum_i) * C_i . h_prev      (h: (P, N))
    h = h_ref[...]
    y_inter = jnp.exp(cum)[:, None] * jnp.dot(
        Cb, h.T, preferred_element_type=jnp.float32)

    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: h <- exp(cum[-1]) h + x^T (B * dt * exp(cum[-1]-cum))
    decay_end = jnp.exp(cum[C_len - 1] - cum)             # (C,)
    bw = Bb * (decay_end * dt)[:, None]                   # (C, N)
    h_ref[...] = (jnp.exp(cum[C_len - 1]) * h
                  + jnp.dot(xb.T, bw, preferred_element_type=jnp.float32))

    @pl.when(c == nc - 1)
    def _emit():
        hout_ref[0, 0] = h_ref[...]


def ssd_pallas(x, dt, A, Bm, Cm, *, chunk: int = 256,
               interpret: bool = False):
    """x (B,H,S,P); dt (B,H,S); A (H,); Bm/Cm (B,G,S,N) with H % G == 0.

    Returns (y (B,H,S,P), h_final (B,H,P,N))."""
    B, H, S, P = x.shape
    G, N = Bm.shape[1], Bm.shape[3]
    rep = H // G
    chunk = min(chunk, S)
    assert S % chunk == 0
    nc = S // chunk

    try:
        cp = pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"))
    except Exception:
        cp = None

    return pl.pallas_call(
        functools.partial(_kernel, nc=nc),
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk), lambda b, h, c: (b, h, c)),
            pl.BlockSpec((1, 1), lambda b, h, c: (h, 0)),
            pl.BlockSpec((1, 1, chunk, N),
                         lambda b, h, c, _rep=rep: (b, h // _rep, c, 0)),
            pl.BlockSpec((1, 1, chunk, N),
                         lambda b, h, c, _rep=rep: (b, h // _rep, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=cp,
        interpret=interpret,
        name="ssd",
    )(x, dt, A.reshape(H, 1), Bm, Cm)
