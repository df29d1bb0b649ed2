"""Pallas bodies for the fused low-bit cohort-decode step.

Three kernels, one HBM pass each (paper §3.2 "Quantization" + §3.3 decode
path — "avoid separate dequant passes; write the new KV position, not the
window"):

* :func:`fused_qkv_pallas` — in-VMEM weight unpack (q4/q8 codes + scales,
  the fp16 weight never materializes to HBM) feeding the three QKV GEMMs
  of one attention sublayer;
* :func:`fused_mlp_pallas` — the same unpack fused with the gate/up GEMMs,
  activation, and down GEMM, on a grid over ``d_ff`` tiles that
  accumulates the down projection in f32;
* :func:`kv_row_scatter_pallas` — the paged single-position K/V scatter:
  grid (layer tiles, bc), scalar-prefetched (block, offset) per cohort
  row, the pool aliased in place (donation) and ONLY each live row's own
  block rewritten — sentinel rows (``blk == n_blocks``) write nothing.

Layout rules the TPU compiler (Mosaic) imposes, and how each is met:

* every GEMM is 2-D and accumulates in f32 (``preferred_element_type``);
  the QKV weights are presented as ``(D, H*hd)`` slabs, reshaped outside
  the kernel, and the outputs are reshaped back to heads outside too;
* no block holds more than the default scoped VMEM: ``fused_mlp`` tiles
  ``d_ff`` (:func:`ff_tile`), and packed weights unpack a few rows at a
  time into a bf16 VMEM scratch, so no whole f32 matrix is ever built;
* a column tile of a packed matrix is not lane-aligned (``tile / per_word``
  int32 words), so those operands are pre-tiled outside the kernel to
  ``(n_tiles, rows, cols / n_tiles)`` and indexed on the leading axis.
* the scatter works on the lane-dense pool ``(L, n_blocks, bs, KV*hd)``,
  the layout the pool rests in and the gather reads, so XLA copies no pool
  into a layout of the kernel's own (with ``(KV, hd)`` minor, llava's 2 x
  64, Mosaic's layout was 2x lane-padded and every step copied the pool
  there and back).  Its pool block is a row's whole ``(bs, KV*hd)`` block
  for a tile of layers: a one-row ``(1, KV*hd)`` block is refused (the
  second-minor block dim must be a multiple of 8 or the whole dim), and so
  is storing at a dynamic sublane offset into a larger block (Mosaic
  cannot prove the index a multiple of 8), so the row goes in by an iota
  mask over the block, a read-modify-write.

Numerics: the unpack replicates ``core.quantize.dequantize``'s cast chain
(int unpack -> f32 -> x scales -> cast) bit for bit, and each GEMM rounds
its f32 accumulator to the activation dtype where the composed
``jnp.einsum`` chain does, so ``fused_qkv`` equals the composed oracle in
interpret mode.  ``fused_mlp`` does not: its activation runs in f32 and
rounds once (the composed chain rounds after every bf16 op), and with
several ``d_ff`` tiles the down projection sums tile by tile.  The fused
step therefore agrees with the composed one within a stated tolerance
(tests/test_fused_decode.py), not bit for bit.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quantize import QTensor, QuantSpec
from repro.models.common import activation
from repro.models.mlp import GATED

# bytes the double-buffered weight tiles of one fused_mlp grid step may
# take: half of the 16 MiB default scoped VMEM, leaving the rest for the
# unpack scratch and the compiler's temporaries
_TILE_BUDGET = 8 << 20
# bytes of one unpack chunk's (rows, words, per_word) int32 intermediate,
# whose per_word axis pads to 128 lanes
_UNPACK_BUDGET = 1 << 20


def _unpack(codes, scales, spec: QuantSpec, dtype):
    """(r, words) int32 codes + (r, groups) scales -> (r, words*per_word)
    weights, numerically identical to core.quantize.dequantize."""
    pw, bits = spec.per_word, spec.bits
    r, words = codes.shape
    shifts = jax.lax.broadcasted_iota(jnp.int32, (r, words, pw), 2) * bits
    field = jnp.bitwise_and(jnp.right_shift(codes[:, :, None], shifts),
                            (1 << bits) - 1)
    sign = 1 << (bits - 1)
    q = jnp.where(field >= sign, field - (1 << bits), field)
    q = q.reshape(r, words * pw).astype(jnp.float32)
    groups, g = scales.shape[1], spec.group_size
    s = jnp.broadcast_to(scales.astype(jnp.float32)[:, :, None],
                         (r, groups, g)).reshape(r, groups * g)
    return (q * s).astype(dtype)


def _row_chunk(rows: int, words: int) -> int:
    """Rows unpacked per step: a multiple of 8 dividing ``rows`` whose
    padded intermediate stays inside ``_UNPACK_BUDGET``."""
    cap = max(8, _UNPACK_BUDGET // (words * 128 * 4))
    best = rows
    for rc in range(8, min(rows, cap) + 1, 8):
        if rows % rc == 0:
            best = rc
    return best


class _Weight:
    """One weight matrix (dense or packed) as kernel operands, viewed as
    a 2-D ``(rows, cols)`` slab: ``(D, H, hd)`` projections merge their
    head axes, packed codes and scales likewise."""

    def __init__(self, w, rows: int, cols: int):
        self.quant = isinstance(w, QTensor)
        self.dtype, self.cols = w.dtype, cols
        self.padded = False
        if not self.quant:
            self.arrays = [w.reshape(rows, cols)]
            return
        self.spec = w.spec
        assert w.spec.group_size % w.spec.per_word == 0, w.spec
        self.arrays = [w.codes.reshape(rows, -1), w.scales.reshape(rows, -1)]
        # quantize pads the packed (last) axis to whole groups; the
        # padded width is cut back per head after the unpack
        self.kp = w.codes.shape[-1] * w.spec.per_word
        self.logical = w.shape[-1]
        self.heads = w.shape[-2] if w.codes.ndim == 3 else 1
        self.padded = self.kp != self.logical

    @property
    def needs_scratch(self) -> bool:
        return self.quant and not self.padded

    def scratch_shape(self, rows: int, cols: int):
        return [pltpu.VMEM((rows, cols), self.dtype)] \
            if self.needs_scratch else []

    def materialize(self, refs, scratch):
        """The weight as a (rows, cols) value: dense straight from its
        ref; packed unpacked chunk by chunk into ``scratch``."""
        if not self.quant:
            return refs[0][...]
        codes_ref, scales_ref = refs
        rows, words = codes_ref.shape
        if self.padded:
            # padded packings only occur at test widths: unpack whole
            w = _unpack(codes_ref[...], scales_ref[...], self.spec,
                        self.dtype)
            w = w.reshape(rows, self.heads, self.kp)[:, :, :self.logical]
            return w.reshape(rows, self.heads * self.logical)
        rc = _row_chunk(rows, words)

        def chunk(i, carry):
            r0 = pl.multiple_of(i * rc, rc)
            scratch[pl.ds(r0, rc), :] = _unpack(
                codes_ref[pl.ds(r0, rc), :], scales_ref[pl.ds(r0, rc), :],
                self.spec, self.dtype)
            return carry

        jax.lax.fori_loop(0, rows // rc, chunk, 0)
        return scratch[...]


def _whole(a):
    return pl.BlockSpec(a.shape, lambda *_, _r=a.ndim: (0,) * _r)


def _dot(x, w):
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def fused_qkv_pallas(h, wq, wk, wv,
                     bq: Optional[jnp.ndarray] = None,
                     bk: Optional[jnp.ndarray] = None,
                     bv: Optional[jnp.ndarray] = None, *,
                     interpret: bool = False):
    """h (bc,1,D) x wq/wk/wv (D,H|KV,hd) [dense or packed] -> q,k,v.

    One pallas_call: the packed codes stream HBM->VMEM once, unpack in
    VMEM, and feed all three projections; biases are fused adds."""
    bc, _, D = h.shape
    heads = [w.shape[-2:] for w in (wq, wk, wv)]
    ws = [_Weight(w, D, n * hd) for w, (n, hd) in zip((wq, wk, wv), heads)]
    biases = [b.reshape(1, -1) for b in (bq, bk, bv) if b is not None]
    assert len(biases) in (0, 3)
    operands = [h.reshape(bc, D)] + [a for w in ws for a in w.arrays] \
        + biases
    out_shapes = tuple(jax.ShapeDtypeStruct((bc, w.cols), h.dtype)
                       for w in ws)
    scratch = [s for w in ws for s in w.scratch_shape(D, w.cols)]

    def body(*refs):
        it = iter(refs)
        x = next(it)[...]
        w_refs = [[next(it) for _ in w.arrays] for w in ws]
        b_refs = [next(it) for _ in biases]
        o_refs = [next(it) for _ in ws]
        for i, (w, wr, o_ref) in enumerate(zip(ws, w_refs, o_refs)):
            sc = next(it) if w.needs_scratch else None
            y = _dot(x, w.materialize(wr, sc)).astype(o_ref.dtype)
            if b_refs:
                y = y + b_refs[i][...]
            o_ref[...] = y

    outs = pl.pallas_call(
        body,
        grid=(1,),
        in_specs=[_whole(a) for a in operands],
        out_specs=[_whole(s) for s in out_shapes],
        out_shape=out_shapes,
        scratch_shapes=scratch,
        interpret=interpret,
        name="fused_qkv",
    )(*operands)
    return tuple(o.reshape(bc, 1, n, hd) for o, (n, hd) in zip(outs, heads))


def ff_tile(d_ff: int, d_model: int, n_mats: int, itemsize: int,
            specs=()) -> int:
    """The ``d_ff`` tile of :func:`fused_mlp_pallas`: the largest
    multiple of 128 dividing ``d_ff`` (and every packed spec's group and
    word) whose double-buffered dense tiles fit ``_TILE_BUDGET``; the
    whole of ``d_ff`` when no such multiple exists."""
    limit = _TILE_BUDGET // (2 * n_mats * d_model * itemsize)
    step = 128
    for s in specs:
        step = max(step, s.group_size, s.per_word)
    best = None
    for t in range(step, min(d_ff, limit) + 1, step):
        if d_ff % t == 0 and all(t % s.group_size == 0 and
                                 t % s.per_word == 0 for s in specs):
            best = t
    return best or d_ff


def _col_tiles(a, n: int, lane_ok: bool):
    """Operand + BlockSpec for column tile ``i`` of a 2-D (R, C) array:
    a direct (R, C/n) block when lane-aligned, else pre-tiled outside the
    kernel to (n, R, C/n) and indexed on the leading axis."""
    R, C = a.shape
    if n == 1 or (lane_ok and (C // n) % 128 == 0):
        return a, pl.BlockSpec((R, C // n), lambda i: (0, i))
    tiled = a.reshape(R, n, C // n).transpose(1, 0, 2)
    return tiled, pl.BlockSpec((None, R, C // n), lambda i: (i, 0, 0))


def _row_tiles(a, n: int):
    R, C = a.shape
    return a, pl.BlockSpec((R // n, C), lambda i: (i, 0))


def fused_mlp_pallas(h, w_up, w_down, w_gate=None, *,
                     act: str, interpret: bool = False):
    """h (bc,1,D) -> gate/up GEMMs, activation, down GEMM, one kernel.

    Mirrors models/mlp.apply_mlp einsum-for-einsum on a grid over
    ``d_ff`` tiles: each step computes its tile of up/gate, the
    activation, and adds its share of the down projection to an f32
    accumulator; packed weights unpack in VMEM, so the fp16
    d_ff x d_model matrices never hit HBM."""
    bc, _, D = h.shape
    F = w_up.shape[-1]
    cols = [_Weight(w, D, F) for w in (w_up, w_gate) if w is not None]
    down = _Weight(w_down, F, D)
    mats = cols + [down]
    specs = [w.spec for w in mats if w.quant]
    n = 1
    if not any(w.quant and w.padded for w in mats):
        n = F // ff_tile(F, D, len(mats), jnp.dtype(h.dtype).itemsize,
                         specs)
    tf = F // n

    operands, in_specs = [h.reshape(bc, D)], [_whole(h.reshape(bc, D))]
    for w in cols:
        for a in w.arrays:
            op, spec = _col_tiles(a, n, lane_ok=not w.quant)
            operands.append(op)
            in_specs.append(spec)
    for a in down.arrays:
        op, spec = _row_tiles(a, n)
        operands.append(op)
        in_specs.append(spec)
    scratch = [s for w in cols for s in w.scratch_shape(D, tf)]
    scratch += down.scratch_shape(tf, D)
    scratch.append(pltpu.VMEM((bc, D), jnp.float32))
    gated = w_gate is not None

    def body(*refs):
        it = iter(refs)
        x = next(it)[...]
        col_refs = [[next(it) for _ in w.arrays] for w in cols]
        down_refs = [next(it) for _ in down.arrays]
        out_ref = next(it)
        col_scr = [next(it) if w.needs_scratch else None for w in cols]
        down_scr = next(it) if down.needs_scratch else None
        acc_ref = next(it)
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        # up/gate round to the activation dtype as the composed einsums
        # do; the activation itself runs in f32 (Mosaic has no bf16
        # logistic) and rounds once
        ys = [_dot(x, w.materialize(r, s)).astype(x.dtype).astype(
            jnp.float32) for w, r, s in zip(cols, col_refs, col_scr)]
        if gated:
            mid = activation(GATED[act])(ys[1]) * ys[0]
        else:
            mid = activation(act)(ys[0])
        acc_ref[...] += _dot(mid.astype(x.dtype),
                             down.materialize(down_refs, down_scr))

        @pl.when(i == n - 1)
        def _():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)

    out = pl.pallas_call(
        body,
        grid=(n,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bc, D), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((bc, D), h.dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="fused_mlp",
    )(*operands)
    return out.reshape(h.shape)


def _visits(blk, n_blocks: int):
    """The pool block each cohort row's grid step visits: a live row its
    own; a sentinel row (``blk == n_blocks``) its nearest live
    neighbour's — the one before it, else the one after (block 0 if no
    row is live).  Live rows own distinct blocks, so every block's visits
    are consecutive steps, and the pipeline neither refetches nor writes
    back a block between them: a sentinel row moves no pool bytes."""
    bc = blk.shape[0]
    idx = jnp.arange(bc, dtype=jnp.int32)
    live = blk < n_blocks
    before = jax.lax.cummax(jnp.where(live, idx, -1))
    after = jax.lax.cummin(jnp.where(live, idx, bc), reverse=True)
    src = jnp.where(before >= 0, before, after)
    return jnp.where(src < bc, blk[jnp.minimum(src, bc - 1)], 0)


def _layer_tile(L: int, block_bytes: int) -> int:
    """Layers per scatter block: the largest divisor of ``L`` whose K and
    V blocks, in and out and double-buffered, fit ``_TILE_BUDGET``."""
    return max((t for t in range(1, L + 1)
                if L % t == 0 and 8 * t * block_bytes <= _TILE_BUDGET),
               default=1)


def kv_row_scatter_pallas(blk, off, k_rows, v_rows, k_pool, v_pool, *,
                          interpret: bool = False):
    """Scatter each group's new K/V position per cohort row into the pool.

    k_pool/v_pool lane-dense (L, n_blocks, bs, KV*hd), donated (aliased in
    place); k_rows/v_rows (L, bc, ...) with KV*hd elements per row;
    blk/off (bc,) int32 scalar-prefetched.  Grid (layer tiles, bc): each
    step reads the row's whole (bs, KV*hd) block of a tile of layers and
    writes it back with the one new row selected in by an iota mask.
    Mosaic refuses the one-row block (its second-minor dim must be a
    multiple of 8 or the whole dim) and a dynamic sublane index into a
    block, so the row is written by a read-modify-write of its block, 16
    KB per layer and row at llava's widths.  A block is copied in on its
    first visit only (:func:`_visits`); sentinel rows write nothing —
    the block they revisit leaves the kernel as it came in or as its live
    row wrote it, the drop semantics of the composed
    ``.at[...].set(mode="drop")``."""
    L, n_blocks, bs, F = k_pool.shape
    bc = k_rows.shape[1]
    lt = _layer_tile(L, bs * F * k_pool.dtype.itemsize)
    k_rows = k_rows.reshape(L, bc, 1, F)
    v_rows = v_rows.reshape(L, bc, 1, F)

    row_spec = pl.BlockSpec((lt, 1, 1, F),
                            lambda g, b, blk, vis, off: (g, b, 0, 0))
    pool_spec = pl.BlockSpec((lt, 1, bs, F),
                             lambda g, b, blk, vis, off: (g, vis[b], 0, 0))

    def body(blk_ref, vis_ref, off_ref, krow_ref, vrow_ref, kin_ref,
             vin_ref, kout_ref, vout_ref):
        b = pl.program_id(1)
        first = (b == 0) | (vis_ref[b] != vis_ref[jnp.maximum(b - 1, 0)])

        @pl.when(first)
        def _():
            kout_ref[...] = kin_ref[...]
            vout_ref[...] = vin_ref[...]

        @pl.when(blk_ref[b] < n_blocks)
        def _():
            at = jax.lax.broadcasted_iota(jnp.int32, kout_ref.shape, 2) \
                == off_ref[b]
            for row_ref, out_ref in ((krow_ref, kout_ref),
                                     (vrow_ref, vout_ref)):
                row = jnp.broadcast_to(row_ref[...].astype(out_ref.dtype),
                                       out_ref.shape)
                out_ref[...] = jnp.where(at, row, out_ref[...])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(L // lt, bc),
        in_specs=[row_spec, row_spec, pool_spec, pool_spec],
        out_specs=[pool_spec, pool_spec],
    )
    return pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)),
        input_output_aliases={5: 0, 6: 1},
        # in order: a block's visits must be consecutive steps
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="kv_row_scatter",
    )(blk, _visits(blk, n_blocks), off, k_rows, v_rows, k_pool, v_pool)
