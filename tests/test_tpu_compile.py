"""Ahead-of-time compiles of the fused cohort-decode kernels for a TPU v5e.

Interpret mode cannot see what the TPU compiler (Mosaic) refuses: a
block over the scoped VMEM limit, a matmul that does not accumulate in
32 bits, a shape cast it has no layout for.  These tests compile each
kernel of the served decode path, and one whole jitted fused cohort step,
at llava-onevision-0.5b's published widths for a described ``v5e:2x2``
topology — nothing runs, so they say nothing about results or times.
They also read the optimized programs of the cohort step and the prefill
insert at the benchmark's pool sizes for one thing: that no instruction
copies the whole paged pool into another layout.

The topology is described inside a module fixture (never at import:
only one process may load the TPU library, and the test workers each
import every test file), and the persistent compile cache is off while
these compiles run: a compile for a described chip is written to it but
cannot be read back without one.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.fused_decode import cohort_step
from repro.kernels.fused_decode import kernel as K
from repro.launch.steps import abstract_params
from repro.serving.kv_cache import paged_positions, serve_kv_insert_blocks

BC = 4            # cohort rows
N_BLOCKS, BS = 64, 64


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def llava():
    return get_config("llava-onevision-0.5b")


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _layer0(cfg, quant):
    """Shapes of decoder layer 0 (dense or the W4A16 g32 serving
    profile) at published widths."""
    p = abstract_params(cfg, "nanomind-serve" if quant else None)
    return jax.eval_shape(lambda p: jax.tree.map(lambda a: a[0], p),
                          p["layers"])[0]


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _h(cfg, one_chip):
    return jax.ShapeDtypeStruct((BC, 1, cfg.d_model), jnp.bfloat16,
                                sharding=one_chip)


@pytest.mark.parametrize("quant", [False, True], ids=["dense", "q4"])
def test_fused_qkv_compiles(one_chip, llava, quant):
    mix = _on(one_chip, _layer0(llava, quant)["mixer"])
    ws = (mix["wq"], mix["wk"], mix["wv"])
    # the cohort step dequantizes biases before the kernel
    bias = tuple(jax.ShapeDtypeStruct(mix[w].shape[-2:], jnp.bfloat16,
                                      sharding=one_chip)
                 for w, b in (("wq", "bq"), ("wk", "bk"), ("wv", "bv"))
                 if b in mix)
    _compile(lambda h, ws, b: K.fused_qkv_pallas(h, *ws, *b,
                                                 interpret=False),
             _h(llava, one_chip), ws, bias)


@pytest.mark.parametrize("quant", [False, True], ids=["dense", "q4"])
def test_fused_mlp_compiles(one_chip, llava, quant):
    ffn = _on(one_chip, _layer0(llava, quant)["ffn"])
    _compile(lambda h, f: K.fused_mlp_pallas(
        h, f["w_up"], f["w_down"], f.get("w_gate"), act=llava.act,
        interpret=False), _h(llava, one_chip), ffn)


def test_kv_row_scatter_compiles(one_chip, llava):
    L, KV, hd = llava.n_layers, llava.n_kv_heads, llava.hd
    pool = jax.ShapeDtypeStruct((L, N_BLOCKS, BS, KV * hd), jnp.bfloat16,
                                sharding=one_chip)
    rows = jax.ShapeDtypeStruct((L, BC, KV, hd), jnp.bfloat16,
                                sharding=one_chip)
    idx = jax.ShapeDtypeStruct((BC,), jnp.int32, sharding=one_chip)
    _compile(lambda b, o, kr, vr, kp, vp: K.kv_row_scatter_pallas(
        b, o, kr, vr, kp, vp, interpret=False), idx, idx, rows, rows,
        pool, pool)


def test_fused_cohort_step_compiles(one_chip, llava):
    """The engine's whole jitted decode step, fused, all 24 layers and
    the 151,936-token head, with the pool donated as the engine does."""
    cfg = llava
    params = _on(one_chip, abstract_params(cfg))
    L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.hd
    W = 16                                     # blocks per slot

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    leaf = sds((L, N_BLOCKS, BS, KV * hd), jnp.bfloat16)
    pool = ((leaf, leaf),)
    paged = paged_positions(cfg)

    def step(p, tokens, lengths, slot_ids, tables, pool):
        return cohort_step(p, cfg, tokens, lengths, slot_ids, tables, pool,
                           block_size=BS, paged=paged, use_fused=True,
                           interpret=False)

    compiled = jax.jit(step, donate_argnums=(5,)).lower(
        params, sds((BC, 1), jnp.int32), sds((BC,), jnp.int32),
        sds((BC,), jnp.int32), sds((BC, W), jnp.int32), pool).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem is None or mem.temp_size_in_bytes < 16 << 30


def _pool_copies(hlo: str, shape) -> list:
    """Instructions of an optimized HLO module that copy an array of the
    pool's ``shape`` (``copy`` or ``copy-start``, whatever the layouts)."""
    dims = "bf16[" + ",".join(map(str, shape)) + "]"
    found = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = (.*?) copy(?:-start)?\(", line)
        if m and dims in m.group(1):
            found.append(line.strip()[:160])
    return found


# the benchmark's pools: config, layers kept, n_blocks, blocks per slot,
# cohort rows of the step, prompt bucket of the prefill insert
POOLS = {
    "llava-bc1": ("llava-onevision-0.5b", None, 2048, 32, 1, 1024),
    "llava-bc64": ("llava-onevision-0.5b", None, 2048, 32, 64, 1024),
    "qwen2vl-14l-bc16": ("qwen2-vl-7b", 14, 528, 33, 16, 2048),
}


@pytest.mark.parametrize("case", list(POOLS))
def test_no_whole_pool_copy(one_chip, case):
    """The fused cohort step (pool donated, as the engine runs it) and
    the grouped-prefill insert of 4 prompts rest, gather, scatter and
    insert the lane-dense pool in one layout: no copy of it anywhere."""
    arch, n_layers, n_blocks, W, bc, bucket = POOLS[case]
    cfg = get_config(arch)
    if n_layers is not None:
        cfg = dataclasses.replace(cfg, n_layers=n_layers)
    L, KV, hd = cfg.n_layers, cfg.n_kv_heads, cfg.hd

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    shape = (L, n_blocks, BS, KV * hd)
    leaf = sds(shape, jnp.bfloat16)
    paged = paged_positions(cfg)

    def step(p, tokens, lengths, slot_ids, tables, pool):
        return cohort_step(p, cfg, tokens, lengths, slot_ids, tables, pool,
                           block_size=BS, paged=paged, use_fused=True,
                           interpret=False)

    compiled = jax.jit(step, donate_argnums=(5,)).lower(
        _on(one_chip, abstract_params(cfg)), sds((bc, 1), jnp.int32),
        sds((bc,), jnp.int32), sds((bc,), jnp.int32),
        sds((bc, W), jnp.int32), ((leaf, leaf),)).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert _pool_copies(hlo, shape) == []

    K_ = 4
    insert = serve_kv_insert_blocks.lower(
        leaf, sds((L, K_, bucket, KV, hd), jnp.bfloat16),
        sds((K_, bucket // BS), jnp.int32), BS).compile()
    assert _pool_copies(insert.as_text(), shape) == []
