"""Slot- and block-paged KV/SSM caches for continuous batching.

Two pools, same static-shape discipline (the paper's NPU section: never
recompile):

* :class:`SlotCache` — the original flat pool: ``n_slots`` request rows,
  each ``max_len`` wide, per-slot lengths in the cache's ``index``
  vector.  Still the simplest thing that works when every request may
  grow to ``max_len`` anyway; kept as the reference layout.

* :class:`PagedKVCache` — the paged pool the engine's decode cohort
  runs on.  Attention K/V live as fixed-size **blocks** instead of
  per-slot rows; every admitted request owns a **block table**
  (host-side list of granted block ids) and decode gathers its context
  as ``pool[table]``.  SSM / linear-attention state has no length axis,
  so those group positions stay slot-indexed.  Admission *grants* a
  request exactly the blocks its lifetime needs (block-aligned prefill
  bucket + decode growth), charged per slot class
  (``core/scheduler.kv_block_budgets``), and retirement returns them to
  the free deque immediately — the continuous-batching property that a
  finishing request's memory is grantable at the very next step.

A paged leaf is **lane-dense**: ``(L, n_blocks, block_size, KV*hd)``,
the KV heads and head dim fused into one trailing axis.  The TPU tiles
an array's two minor dims by (8, 128); with ``(KV, hd)`` minor (llava's
2 x 64) neither fills 128 lanes, so XLA rests the pool with its *block*
axis on the lanes and every gather, Mosaic scatter and prefill insert
first copies the whole pool into a layout of its own.  With one fused
axis the resting layout, the gather's and the scatter kernel's coincide
and no program relayouts the pool.  The composed ``(KV, hd)`` view exists
only on arrays a row owns: a prefill batch before it lands, the new K/V
rows, the gathered context (``kernels/fused_decode.paged_context``).

Both pools land grouped batch-B prefills in ONE donated strided scatter
per leaf (``insert_many``): the flat pool scatters rows, the paged pool
reshapes the block-aligned prefill ``(L, B, nb*block_size, KV, hd)``
into ``(L, B*nb, block_size, KV*hd)`` and scatters into the owners'
granted blocks.

Out-of-range sentinels make cohort padding free: a padded cohort row
carries slot id ``n_slots`` and block id ``n_blocks`` — device gathers
use ``mode="fill"`` (zeros in, masked by the per-row length), scatters
use ``mode="drop"`` (writes vanish), so no host-side branching per row.
"""
from __future__ import annotations

import functools
from collections import deque
from dataclasses import dataclass
from typing import Any, Deque, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import decoder as dec
from repro.models import model as M


@functools.partial(jax.jit, donate_argnums=(0,))
def serve_kv_insert_slots(pool_leaf, batch_leaf, slots: jnp.ndarray):
    """Write a batch-K cache leaf (L, K, ...) into rows `slots` of the
    (L, B, ...) pools — ONE strided scatter per leaf, donated in place,
    so a grouped batch-B prefill lands in B slots in a single op instead
    of B slot-by-slot merges.  Leaves carry a leading layer-stack dim."""
    return pool_leaf.at[:, slots].set(batch_leaf.astype(pool_leaf.dtype))


@functools.partial(jax.jit, donate_argnums=(0,), static_argnums=(3,))
def serve_kv_insert_blocks(pool_leaf, batch_leaf, block_ids: jnp.ndarray,
                           block_size: int):
    """Paged twin of :func:`serve_kv_insert_slots`: a batch-K prefilled
    attention leaf (L, K, S, ...) with block-aligned S = nb*block_size
    lands in each request's granted blocks — `block_ids` is (K, nb) — as
    ONE donated strided scatter into the lane-dense (L, n_blocks,
    block_size, KV*hd) pool.  Only the batch is reshaped, never the pool.
    Sentinel ids (>= n_blocks) are dropped."""
    L, K, S = batch_leaf.shape[:3]
    nb = S // block_size
    resh = batch_leaf.reshape(L, K * nb, block_size, pool_leaf.shape[-1])
    return pool_leaf.at[:, block_ids.reshape(-1)].set(
        resh.astype(pool_leaf.dtype), mode="drop")


def paged_positions(cfg: ModelConfig) -> Tuple[bool, ...]:
    """Which group positions carry a length-indexed attention K/V cache —
    the positions the paged pool blocks.  Mamba and linear-attention
    state is fixed-size per request, so it stays slot-indexed."""
    return tuple(
        dec.sublayer_spec(cfg, pos)[0] == "attn"
        and dec.cfg_attn_impl(cfg) != "linear"
        for pos in range(dec.group_size(cfg)))


@dataclass
class SlotCache:
    """The pooled decode state + the host-side free list."""

    cfg: ModelConfig
    n_slots: int
    max_len: int

    def __post_init__(self):
        self.cache = M.init_decode_state(self.cfg, self.n_slots, self.max_len,
                                         start_index=0)
        # per-slot lengths (vector index => continuous batching)
        self.cache["index"] = jnp.zeros((self.n_slots,), jnp.int32)
        self.free: Deque[int] = deque(range(self.n_slots))
        self.live: Dict[int, Any] = {}

    # -- admission ----------------------------------------------------------
    def take_slot(self) -> Optional[int]:
        return self.free.popleft() if self.free else None

    def insert(self, slot: int, prefill_cache, prompt_len: int):
        """Merge a batch-1 prefilled cache into the pool at `slot` — the
        K=1 case of :meth:`insert_many`."""
        self.insert_many([slot], prefill_cache, [prompt_len])

    def insert_many(self, slots: List[int], prefill_cache,
                    prompt_lens: List[int]):
        """Merge a batch-K prefilled cache (leaves (L, K, ...)) into K
        pool slots in one strided scatter per leaf — the admission side
        of the grouped prefill: one device op per leaf regardless of how
        many requests the prefill batched."""
        idx = jnp.asarray(slots, jnp.int32)
        self.cache["layers"] = jax.tree.map(
            lambda pool, many: serve_kv_insert_slots(pool, many, idx),
            self.cache["layers"], prefill_cache["layers"])
        self.cache["index"] = self.cache["index"].at[idx].set(
            jnp.asarray(prompt_lens, jnp.int32))

    def release(self, slot: int):
        self.cache["index"] = self.cache["index"].at[slot].set(0)
        self.free.append(slot)

    # -- views --------------------------------------------------------------
    @property
    def lengths(self) -> jnp.ndarray:
        return self.cache["index"]

    def active_mask(self, live_slots) -> jnp.ndarray:
        m = jnp.zeros((self.n_slots,), bool)
        if live_slots:
            m = m.at[jnp.asarray(sorted(live_slots))].set(True)
        return m

    @property
    def nbytes(self) -> int:
        return sum(l.size * l.dtype.itemsize
                   for l in jax.tree.leaves(self.cache))


class PagedKVCache:
    """Block-paged decode state: the device pools plus the host-side
    block allocator (free deques, per-request block tables, per-class
    block accounting, per-slot lengths).

    Device layout, one entry per group position (``paged_positions``):

    * paged (attention K/V): lane-dense leaves ``(L, n_blocks,
      block_size, KV*hd)`` (module docstring);
    * slot state (mamba / linear attention): leaves ``(L, n_slots, ...)``
      exactly as :func:`repro.models.decoder.init_cache` builds them.

    Host bookkeeping is plain Python under the engine's single-threaded
    step loop: ``free`` / ``free_blocks`` are deques (O(1) head pops —
    the old ``free.pop(0)`` was O(n)), ``block_tables[slot]`` is the
    request's granted block-id run, ``used_blocks[slot_class]`` the
    per-class charge ``core/scheduler.kv_block_budgets`` reads, and
    ``lengths`` a host numpy vector (the decode cohort feeds it in as
    the batched ``index``, so retiring or admitting a request never
    touches device state — continuous batching is pure bookkeeping)."""

    def __init__(self, cfg: ModelConfig, n_slots: int, max_len: int, *,
                 block_size: int = 64, total_blocks: Optional[int] = None):
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_len = max_len
        self.block_size = block_size
        self.blocks_per_slot = -(-max_len // block_size)
        # the paged win: total_blocks < n_slots*blocks_per_slot
        # oversubscribes slots against memory (most requests never grow
        # to max_len); default is the flat pool's worst case
        self.n_blocks = (n_slots * self.blocks_per_slot
                         if total_blocks is None else int(total_blocks))
        self.paged = paged_positions(cfg)
        base = dec.init_cache(cfg, n_slots, block_size)
        pool = []
        for pos, paged in enumerate(self.paged):
            if paged:
                # reuse the (L, n_slots, block_size, KV, hd) template
                # leaf for its dtype; blocks replace the slot axis and
                # the heads fuse into one lane axis
                pool.append(jax.tree.map(
                    lambda l: jnp.zeros(
                        (l.shape[0], self.n_blocks, block_size,
                         int(np.prod(l.shape[3:]))), l.dtype),
                    base[pos]))
            else:
                pool.append(base[pos])
        self.pool: Tuple[Any, ...] = tuple(pool)
        self.free: Deque[int] = deque(range(n_slots))
        self.free_blocks: Deque[int] = deque(range(self.n_blocks))
        self.block_tables: Dict[int, List[int]] = {}
        self.slot_class_of: Dict[int, Optional[str]] = {}
        self.used_blocks: Dict[Optional[str], int] = {}
        self.lengths = np.zeros((n_slots,), np.int32)

    # -- admission ----------------------------------------------------------
    @property
    def free_block_count(self) -> int:
        return len(self.free_blocks)

    def take_slot(self) -> Optional[int]:
        return self.free.popleft() if self.free else None

    def grant_blocks(self, slot: int, n: int,
                     slot_class: Optional[str] = None) -> List[int]:
        """Grant `n` KV blocks to `slot`, charged to `slot_class`.
        Admission must have checked ``free_block_count`` (and the class
        budget) first — an unfulfillable or double grant raises."""
        if slot in self.block_tables:
            raise RuntimeError(f"slot {slot} already holds a block grant")
        if n > len(self.free_blocks):
            raise RuntimeError(
                f"grant of {n} blocks with only {len(self.free_blocks)} "
                f"free (admission must check first)")
        blocks = [self.free_blocks.popleft() for _ in range(n)]
        self.block_tables[slot] = blocks
        self.slot_class_of[slot] = slot_class
        self.used_blocks[slot_class] = \
            self.used_blocks.get(slot_class, 0) + n
        return blocks

    def insert(self, slot: int, prefill_cache, prompt_len: int):
        """K=1 case of :meth:`insert_many`."""
        self.insert_many([slot], prefill_cache, [prompt_len])

    def insert_many(self, slots: List[int], prefill_cache,
                    prompt_lens: List[int]):
        """Land a batch-K prefilled cache: attention leaves — prefilled
        at a block-aligned width S = nb*block_size — scatter into each
        request's first nb granted blocks (one donated strided scatter
        per leaf, :func:`serve_kv_insert_blocks`); slot-state leaves scatter by
        slot id exactly like the flat pool."""
        layers = prefill_cache["layers"]
        idx = jnp.asarray(slots, jnp.int32)
        bs = self.block_size
        ids = None
        new_pool = []
        for pos, paged in enumerate(self.paged):
            if paged:
                if ids is None:
                    S = jax.tree.leaves(layers[pos])[0].shape[2]
                    if S % bs:
                        raise RuntimeError(
                            f"prefill width {S} is not block-aligned "
                            f"(block_size {bs})")
                    nb = S // bs
                    host = np.full((len(slots), nb), self.n_blocks,
                                   np.int32)
                    for b, slot in enumerate(slots):
                        tbl = self.block_tables.get(slot, [])
                        if len(tbl) < nb:
                            raise RuntimeError(
                                f"slot {slot} holds {len(tbl)} blocks, "
                                f"prefill needs {nb}")
                        host[b] = tbl[:nb]
                    ids = jnp.asarray(host)
                new_pool.append(jax.tree.map(
                    lambda p, m: serve_kv_insert_blocks(p, m, ids, bs),
                    self.pool[pos], layers[pos]))
            else:
                new_pool.append(jax.tree.map(
                    lambda p, m: serve_kv_insert_slots(p, m, idx),
                    self.pool[pos], layers[pos]))
        self.pool = tuple(new_pool)
        for slot, n in zip(slots, prompt_lens):
            self.lengths[slot] = int(n)

    def release(self, slot: int):
        """Retire a request: its blocks return to the free deque NOW —
        grantable to the next admission, before any device op runs."""
        blocks = self.block_tables.pop(slot, None)
        cls = self.slot_class_of.pop(slot, None)
        if blocks:
            self.used_blocks[cls] = \
                self.used_blocks.get(cls, 0) - len(blocks)
            self.free_blocks.extend(blocks)
        self.lengths[slot] = 0
        self.free.append(slot)

    # -- decode-cohort views ------------------------------------------------
    def bump(self, slot: int):
        """One decode step served this slot: host-side length += 1."""
        self.lengths[slot] += 1

    def gather_tables(self, slots: Sequence[int]) -> np.ndarray:
        """Block tables of `slots` as one (len(slots), blocks_per_slot)
        int32 array, padded with the out-of-range sentinel ``n_blocks``
        (device gathers fill zeros, scatters drop)."""
        out = np.full((len(slots), self.blocks_per_slot), self.n_blocks,
                      np.int32)
        for i, slot in enumerate(slots):
            tbl = self.block_tables.get(slot, ())
            out[i, :len(tbl)] = tbl
        return out

    # -- fleet wire (disaggregated prefill -> decode hand-off) ---------------
    @property
    def slot_lane_bytes(self) -> int:
        """Paged bytes of one whole ``max_len`` lane — what shipping a
        flat per-slot row (``blocks_per_slot`` blocks across every paged
        position) would cost.  The baseline
        :meth:`~repro.core.transport.RemotePrefill.kv_wire_bytes` is
        asserted against: a disaggregated hand-off ships only the
        *written* blocks, so its wire bytes must come in under this."""
        per_block = sum(
            leaf.nbytes // self.n_blocks
            for pos, paged in enumerate(self.paged) if paged
            for leaf in jax.tree.leaves(self.pool[pos]))
        return per_block * self.blocks_per_slot

    def export_blocks(self, slot: int, n_blocks: int) -> List[List[Any]]:
        """Pull one request's prefill-written cache off the device for
        the wire: per group position, the flat leaf list — paged
        positions as ``(L, nb, block_size, KV*hd)`` host arrays holding the
        first ``n_blocks`` granted blocks (the *written* ones — never the
        whole lane), slot-state positions as the request's ``(L, 1,
        ...)`` row.  Tree structure is not exported; the importing pool
        re-derives it from its own treedef (same config both fleets).
        The ``np.asarray`` pulls are the serialization boundary — this
        data is leaving the process, so the device sync is the point."""
        tbl = self.block_tables.get(slot, [])
        if n_blocks > len(tbl):
            raise RuntimeError(
                f"export of {n_blocks} blocks from slot {slot} which "
                f"holds {len(tbl)}")
        ids = jnp.asarray(tbl[:n_blocks], jnp.int32)
        out: List[List[Any]] = []
        for pos, paged in enumerate(self.paged):
            if paged:
                out.append([
                    np.asarray(jnp.take(leaf, ids, axis=1))  # replint: disable=host-sync
                    for leaf in jax.tree.leaves(self.pool[pos])])
            else:
                out.append([
                    np.asarray(leaf[:, slot:slot + 1])  # replint: disable=host-sync
                    for leaf in jax.tree.leaves(self.pool[pos])])
        return out

    def import_blocks(self, slot: int, payload: List[List[Any]]) -> None:
        """Land an :meth:`export_blocks` payload in this pool at `slot`
        (which must already hold a block grant at least as long as the
        payload): paged leaves view as one batch-1 block-aligned prefill
        and reuse the donated :func:`serve_kv_insert_blocks`
        scatter into the slot's own granted blocks; slot-state leaves
        scatter by slot id.  Byte-for-byte: export -> wire -> import
        preserves every leaf exactly (tests/test_transport.py), which is
        what makes disaggregated decode bit-identical to single-process."""
        bs = self.block_size
        tbl = self.block_tables.get(slot)
        idx = jnp.asarray([slot], jnp.int32)
        new_pool = list(self.pool)
        for pos, paged in enumerate(self.paged):
            treedef = jax.tree.structure(self.pool[pos])
            leaves = [jnp.asarray(l) for l in payload[pos]]
            batch = jax.tree.unflatten(treedef, leaves)
            if paged:
                nb = int(payload[pos][0].shape[1])
                if tbl is None or len(tbl) < nb:
                    raise RuntimeError(
                        f"import of {nb} blocks into slot {slot} which "
                        f"holds {0 if tbl is None else len(tbl)}")
                ids = jnp.asarray(  # host block table, no device involved
                    np.asarray(tbl[:nb], np.int32)  # replint: disable=host-sync
                    .reshape(1, nb))
                batch = jax.tree.map(
                    lambda l: l.reshape((l.shape[0], 1, nb * bs)
                                        + l.shape[3:]), batch)
                new_pool[pos] = jax.tree.map(
                    lambda p, m: serve_kv_insert_blocks(p, m, ids, bs),
                    new_pool[pos], batch)
            else:
                new_pool[pos] = jax.tree.map(
                    lambda p, m: serve_kv_insert_slots(p, m, idx),
                    new_pool[pos], batch)
        self.pool = tuple(new_pool)

    # -- invariants / reporting ---------------------------------------------
    def check_block_invariants(self):
        """Raise unless the allocator is conservation-clean: every block
        is free xor granted to exactly one slot (no double grant, no
        orphan), and the per-class charge matches the tables.  The
        property-test hook (tests/test_decode_cohort.py)."""
        granted = [b for t in self.block_tables.values() for b in t]
        if len(granted) != len(set(granted)):
            raise AssertionError(f"double-granted block in "
                                 f"{self.block_tables}")
        free = list(self.free_blocks)
        if len(free) != len(set(free)):
            raise AssertionError(f"duplicate free block in {free}")
        if set(granted) & set(free):
            raise AssertionError("block both granted and free")
        if len(granted) + len(free) != self.n_blocks:
            raise AssertionError(
                f"block leak: {len(granted)} granted + {len(free)} free "
                f"!= {self.n_blocks}")
        by_class: Dict[Optional[str], int] = {}
        for slot, tbl in self.block_tables.items():
            cls = self.slot_class_of.get(slot)
            by_class[cls] = by_class.get(cls, 0) + len(tbl)
        used = {c: n for c, n in self.used_blocks.items() if n}
        if by_class != used:
            raise AssertionError(f"class charge drift: tables say "
                                 f"{by_class}, used_blocks says {used}")

    @property
    def nbytes(self) -> int:
        return sum(l.size * l.dtype.itemsize
                   for l in jax.tree.leaves(self.pool))


def bucket_length(n: int, buckets=(128, 256, 512, 1024, 2048, 4096)) -> int:
    """Static-shape prompt bucketing (paper §NPU: fixed input shapes; we
    pad prompts up to the nearest bucket instead of recompiling)."""
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]
