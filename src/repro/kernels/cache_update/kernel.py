"""Zero-copy KV-cache row update — TABM's donation discipline applied to
the decode state (paper §3.2: "the NPU encoder writes embeddings directly
into a buffer slot ... avoiding copies").

GSPMD lowers a one-token dynamic-update into a select over the full local
cache shard (a ~34 MB read+write per layer per step at the 32k serving
cell).  This kernel aliases the cache in place and touches ONLY the row:

* grid (B,): one program per sequence slot;
* input_output_aliasing pins the cache buffer (donation — no copy);
* the row lands via a VMEM block whose index_map reads the per-slot
  write position from scalar prefetch — HBM traffic is the row itself.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(idx_ref, row_ref, cache_in_ref, cache_out_ref):
    # the out block is the (1, 1, KV, hd) row selected by the index_map;
    # the write covers the whole block — nothing else in the shard moves.
    del cache_in_ref
    cache_out_ref[...] = row_ref[...][:, None].astype(cache_out_ref.dtype)


def cache_row_update_pallas(cache, row, index, *, interpret: bool = False):
    """cache (B,S,KV,hd) donated; row (B,KV,hd); index (B,) int32."""
    B, S, KV, hd = cache.shape

    row_block = pl.BlockSpec((1, 1, KV, hd), lambda b, idx: (b, idx[b], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, KV, hd), lambda b, idx: (b, 0, 0)),   # row
            row_block,                                             # cache-in
        ],
        out_specs=row_block,
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(cache.shape, cache.dtype),
        input_output_aliases={2: 0},       # cache (after the prefetch
                                           # scalar and the row) aliases out
        interpret=interpret,
        name="cache_row_update",
    )(index, row, cache)
