"""Property tests for the hybrid quantization machinery (hypothesis)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, strategies as hst

from repro.core.quantize import (PROFILES, QTensor, QuantPolicy, QuantSpec,
                                 dequantize, quantize, quantize_tree,
                                 dequantize_tree, tree_bytes, unpack_codes)

bits_st = hst.sampled_from([2, 4, 8])
dims_st = hst.tuples(hst.integers(1, 7), hst.integers(8, 130))


@given(bits=bits_st, dims=dims_st, seed=hst.integers(0, 2**31 - 1))
def test_roundtrip_error_bound(bits, dims, seed):
    """Per-group error bound with the *chosen* scale s: unclipped values sit
    within s/2 of their code, clipped outliers within amax - s*qmax.  The
    MSE scale search (scale_search > 1) may shrink s below amax/qmax, so the
    bound uses qt.scales rather than assuming the max-abs scale; the search
    must also never do worse than max-abs in group MSE."""
    spec = QuantSpec(bits, group_size=32)
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.standard_normal(dims), jnp.float32)
    qt = quantize(w, spec)
    dq = dequantize(qt)
    assert dq.shape == w.shape and dq.dtype == w.dtype
    # per-group bound with the actual scale
    pad = (-dims[-1]) % 32
    wp = np.pad(np.asarray(w), [(0, 0)] * (w.ndim - 1) + [(0, pad)])
    grp = wp.reshape(*wp.shape[:-1], -1, 32)
    amax = np.abs(grp).max(-1)
    s = np.asarray(qt.scales, np.float64)
    bound = np.maximum(s / 2, amax - s * qt.spec.qmax) + 1e-6
    err = np.abs(np.asarray(dq) - np.asarray(w))
    errp = np.pad(err, [(0, 0)] * (w.ndim - 1) + [(0, pad)])
    err_grp = errp.reshape(*wp.shape[:-1], -1, 32).max(-1)
    assert np.all(err_grp <= bound)
    # the searched scale improves (or matches) max-abs in squared error
    base = dequantize(quantize(w, QuantSpec(bits, group_size=32,
                                            scale_search=1)))
    mse = float(jnp.sum((dq - w) ** 2))
    mse_base = float(jnp.sum((base - w) ** 2))
    assert mse <= mse_base + 1e-6


@given(bits=bits_st, seed=hst.integers(0, 2**31 - 1))
def test_pack_unpack_codes_exact(bits, seed):
    spec = QuantSpec(bits, group_size=32)
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.standard_normal((4, 64)), jnp.float32)
    qt = quantize(w, spec)
    codes = unpack_codes(qt.codes, spec)
    assert int(codes.max()) <= spec.qmax
    assert int(codes.min()) >= spec.qmin


def test_qtensor_is_pytree(key):
    w = jax.random.normal(key, (16, 64))
    qt = quantize(w, QuantSpec(4))
    leaves, treedef = jax.tree_util.tree_flatten(qt)
    assert len(leaves) == 2
    qt2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert jnp.allclose(dequantize(qt2), dequantize(qt))
    # flows through jit
    out = jax.jit(lambda q: dequantize(q).sum())(qt)
    assert jnp.isfinite(out)


def test_bits_monotone_error(key):
    """Fig. 7's qualitative ordering: more bits -> less error."""
    w = jax.random.normal(key, (64, 256))
    errs = []
    for bits in (2, 4, 8):
        dq = dequantize(quantize(w, QuantSpec(bits)))
        errs.append(float(jnp.mean(jnp.abs(dq - w))))
    assert errs[0] > errs[1] > errs[2]


def test_policy_profiles_label_bricks(key):
    pol = PROFILES["nanomind-default"]
    assert pol.label_for("vis_proj/w1") == "fp16"
    assert pol.label_for("embed") == "fp16"
    assert pol.label_for("layers/0/mixer/wq") == "q4f16"
    assert pol.label_for("lm_head") == "q4f16"


def test_quantize_tree_and_memory_accounting(key):
    from repro.configs import get_config
    from repro.launch.steps import init_params
    cfg = get_config("llava-onevision-0.5b").reduced()
    params = init_params(key, cfg)
    full = tree_bytes(params)
    q4 = quantize_tree(params, PROFILES["all-q4"])
    q4_bytes = tree_bytes(q4)
    assert q4_bytes < full  # int4+scales < bf16
    # hybrid: vision stays fp16 -> bigger than all-q4, smaller than full
    hybrid = tree_bytes(quantize_tree(params, PROFILES["nanomind-default"]))
    assert q4_bytes <= hybrid <= full
    # dequantize_tree restores shapes/dtypes
    dq = dequantize_tree(q4)
    for a, b in zip(jax.tree.leaves(dq), jax.tree.leaves(params)):
        assert a.shape == b.shape and a.dtype == b.dtype


def test_quantized_model_still_predicts(key):
    """W4A16 forward stays close to bf16 (the paper's '4-bit LLMs are
    sufficient' claim at smoke scale)."""
    from repro.configs import get_config
    from repro.launch.steps import init_params
    from repro.models.model import lm_forward
    cfg = get_config("stablelm-1.6b").reduced(n_layers=2)
    params = init_params(key, cfg)
    tokens = jnp.arange(32)[None] % 100 + 3
    ref, _ = lm_forward(params, cfg, tokens)
    dq = dequantize_tree(quantize_tree(params, PROFILES["all-q4"]))
    out, _ = lm_forward(dq, cfg, tokens)
    # a random-init model has near-uniform logits, so top-1 flips easily;
    # the robust check is logit closeness + above-chance agreement
    err = jnp.max(jnp.abs(out[..., :cfg.vocab_size]
                          - ref[..., :cfg.vocab_size]))
    rel = float(err) / (float(jnp.max(jnp.abs(ref[..., :cfg.vocab_size])))
                        + 1e-9)
    assert rel < 1.0                               # same logit scale
    agree = jnp.mean((jnp.argmax(out, -1) == jnp.argmax(ref, -1))
                     .astype(jnp.float32))
    # random-init logits are near-uniform so q4 flips many argmaxes; the
    # signal is agreement FAR above chance (1/512).  Trained-model quality
    # is validated in tests/test_serve_quant.py.
    assert float(agree) > 100.0 / cfg.vocab_size
