"""Hybrid per-brick quantization (paper §3.2 "Quantization").

NANOMIND's key quantization idea is *hybrid* precision: because the model is
decomposed into bricks, each brick gets its own bit-width (vision encoder
FP16/INT8, decoder W4A16 or W2A16, embedding FP16).  This module provides

* :class:`QuantSpec` — bits (2/4/8), group size, symmetric group-wise scheme;
* :class:`QTensor` — a pytree-registered packed tensor (int32 words holding
  32/bits codes + per-group scales) that flows through jit/pjit/shardings;
* :func:`quantize` / :func:`dequantize` — round-trip with the max-abs
  group-wise scale (the GGUF/K-quant-style scheme the paper builds on);
* :func:`quantize_tree` / :func:`dequantize_tree` — per-brick application
  driven by a :class:`QuantPolicy` (the paper's ``em-fp16 vis-fp16 dec-q4f16``
  label format);
* weight-memory accounting used by the scheduler's cost model.

Packing layout: codes are packed along the **last** axis, ``32 // bits``
values per int32 word, with per-group scales over contiguous groups of the
last axis.  XLA fuses ``dequantize`` into the consuming matmul (the W4A16
"unpack + rescale in-register" pattern); the explicit fused MXU kernel for
the hot GEMMs is :mod:`repro.kernels.dequant_gemm`.
"""
from __future__ import annotations

import dataclasses
import re
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "QuantSpec", "QTensor", "quantize", "dequantize", "quantize_tree",
    "dequantize_tree", "QuantPolicy", "PROFILES", "tree_bytes",
    "prune_weights", "parse_label",
]


@dataclass(frozen=True)
class QuantSpec:
    """Group-wise symmetric quantization spec.

    ``scale_search > 1`` turns on MSE-optimal scale refinement: instead of
    the plain max-abs scale, each group tries ``scale_search`` shrunken
    candidates in ``[scale_shrink, 1.0] * amax/qmax`` and keeps the one
    minimizing the group's round-trip squared error (the K-quant refinement;
    clipping the odd outlier buys finer resolution for the bulk).  The
    max-abs scale is always a candidate, so exactly-representable groups
    still round-trip bit-exactly.
    """

    bits: int                  # 2 | 4 | 8
    group_size: int = 64       # values per scale group (along last axis)
    scale_dtype: str = "float32"
    scale_search: int = 8      # MSE scale-grid size; <=1 -> plain max-abs
    scale_shrink: float = 0.75  # smallest candidate as a fraction of max-abs

    def __post_init__(self):
        assert self.bits in (2, 4, 8), self.bits
        assert 32 % self.bits == 0
        assert 0.0 < self.scale_shrink <= 1.0

    @property
    def per_word(self) -> int:
        return 32 // self.bits

    @property
    def qmax(self) -> int:
        return (1 << (self.bits - 1)) - 1   # 1, 7, 127

    @property
    def qmin(self) -> int:
        return -(1 << (self.bits - 1))      # -2, -8, -128


@jax.tree_util.register_pytree_node_class
@dataclass
class QTensor:
    """Packed quantized tensor.  Pytree: children = (codes, scales)."""

    codes: jnp.ndarray          # int32, shape (..., K // per_word)
    scales: jnp.ndarray         # shape (..., K // group_size)
    spec: QuantSpec             # static
    shape: Tuple[int, ...]      # original logical shape (static)
    dtype: Any                  # original dtype (static)

    def tree_flatten(self):
        return (self.codes, self.scales), (self.spec, self.shape, self.dtype)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], *aux)

    @property
    def nbytes(self) -> int:
        return int(self.codes.size * 4 + self.scales.size
                   * jnp.dtype(self.spec.scale_dtype).itemsize)

    def __repr__(self):
        return (f"QTensor(w{self.spec.bits}, shape={self.shape}, "
                f"g={self.spec.group_size})")


def _pad_last(x, multiple: int):
    k = x.shape[-1]
    pad = (-k) % multiple
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x, k


def _mse_scale(grp: jnp.ndarray, scale: jnp.ndarray,
               spec: QuantSpec) -> jnp.ndarray:
    """Per-group MSE-optimal scale over a shrink grid.

    grp (..., G, g) fp32 groups; scale (..., G, 1) the max-abs scale.
    Candidates run shrink -> 1.0 so a zero-error max-abs group (already
    exactly representable) wins its ties via the final argmin order below.
    """
    fr = jnp.linspace(spec.scale_shrink, 1.0, spec.scale_search,
                      dtype=jnp.float32)
    cand = scale[..., None] * fr                        # (..., G, 1, n)
    safe = jnp.where(cand == 0, 1.0, cand)
    q = jnp.clip(jnp.round(grp[..., None] / safe), spec.qmin, spec.qmax)
    err = jnp.sum((q * cand - grp[..., None]) ** 2, axis=-2)   # (..., G, n)
    # prefer the LARGEST candidate among exact ties (index of last min):
    # flip so argmin lands on fr=1.0 first, then map the index back.
    best = (fr.shape[0] - 1) - jnp.argmin(err[..., ::-1], axis=-1)
    return jnp.take_along_axis(cand[..., 0, :], best[..., None], axis=-1)


def quantize(w: jnp.ndarray, spec: QuantSpec) -> QTensor:
    """Group-wise symmetric quantization along the last axis."""
    orig_shape, orig_dtype = w.shape, w.dtype
    wf = w.astype(jnp.float32)
    wf, k = _pad_last(wf, max(spec.group_size, spec.per_word))
    kp = wf.shape[-1]
    g = spec.group_size
    grp = wf.reshape(*wf.shape[:-1], kp // g, g)
    amax = jnp.max(jnp.abs(grp), axis=-1, keepdims=True)
    scale = amax / spec.qmax
    if spec.scale_search > 1:
        scale = _mse_scale(grp, scale, spec)
    safe = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(grp / safe), spec.qmin, spec.qmax).astype(jnp.int32)
    q = q.reshape(*wf.shape[:-1], kp)
    # pack: per_word codes -> one int32 (two's-complement field of `bits`)
    pw = spec.per_word
    mask = (1 << spec.bits) - 1
    qu = jnp.bitwise_and(q, mask)                     # unsigned field
    qu = qu.reshape(*wf.shape[:-1], kp // pw, pw)
    shifts = (jnp.arange(pw, dtype=jnp.int32) * spec.bits)
    words = jnp.sum(jnp.left_shift(qu, shifts), axis=-1).astype(jnp.int32)
    scales = scale[..., 0].astype(spec.scale_dtype)
    return QTensor(words, scales, spec, orig_shape, orig_dtype)


def unpack_codes(codes: jnp.ndarray, spec: QuantSpec) -> jnp.ndarray:
    """int32 words -> signed integer codes (..., K) in int32."""
    pw = spec.per_word
    shifts = (jnp.arange(pw, dtype=jnp.int32) * spec.bits)
    field = jnp.right_shift(codes[..., None], shifts)
    field = jnp.bitwise_and(field, (1 << spec.bits) - 1)
    # sign-extend the `bits`-wide field
    sign = 1 << (spec.bits - 1)
    q = jnp.where(field >= sign, field - (1 << spec.bits), field)
    return q.reshape(*codes.shape[:-1], codes.shape[-1] * pw)


def dequantize(qt: QTensor) -> jnp.ndarray:
    q = unpack_codes(qt.codes, qt.spec).astype(jnp.float32)
    g = qt.spec.group_size
    kp = q.shape[-1]
    q = q.reshape(*q.shape[:-1], kp // g, g)
    w = q * qt.scales.astype(jnp.float32)[..., None]
    w = w.reshape(*q.shape[:-2], kp)[..., :qt.shape[-1]]
    return w.astype(qt.dtype)


# ---------------------------------------------------------------------------
# activation-aware magnitude pruning (EdgeMM-style semi-structured sparsity)
# ---------------------------------------------------------------------------


def prune_weights(w: jnp.ndarray, sparsity: float,
                  act_scale: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Zero the lowest-scoring ``sparsity`` fraction of each last-axis row.

    Score is Wanda-style ``|W| * act_scale`` — ``act_scale`` is a per-input-
    feature activation magnitude (shape broadcastable to the last axis, e.g.
    the RMS of calibration activations).  Without it the score degrades to
    plain magnitude.  Rows are thresholded independently so every output
    keeps its strongest inputs; composes with :func:`quantize` (prune first,
    then group-quantize the survivors)."""
    if sparsity <= 0.0:
        return w
    assert 0.0 < sparsity < 1.0, sparsity
    wf = w.astype(jnp.float32)
    score = jnp.abs(wf)
    if act_scale is not None:
        score = score * jnp.abs(jnp.asarray(act_scale, jnp.float32))
    thresh = jnp.quantile(score, sparsity, axis=-1, keepdims=True)
    return jnp.where(score > thresh, wf, 0.0).astype(w.dtype)


# ---------------------------------------------------------------------------
# per-brick policies (the paper's Module–Quantization label format, Fig. 7)
# ---------------------------------------------------------------------------

# label -> spec; fp16/bf16 mean "leave unquantized"
_LABEL_SPECS: Dict[str, Optional[QuantSpec]] = {
    "fp16": None,
    "bf16": None,
    "q8f16": QuantSpec(8),
    "q4f16": QuantSpec(4),
    "q2f16": QuantSpec(2),
}

# composite labels append "-sp<pct>" for activation-aware pruning before
# quantization, e.g. "q4f16-sp50" = prune 50% then W4A16
_SP_RE = re.compile(r"^(?P<base>.+?)-sp(?P<pct>\d{1,2})$")


def parse_label(label: str) -> Tuple[Optional[QuantSpec], float]:
    """'q4f16-g32-sp50' -> (QuantSpec(4, 32), 0.50); plain -> (spec, 0.0)."""
    sparsity = 0.0
    m = _SP_RE.match(label)
    if m:
        sparsity = int(m.group("pct")) / 100.0
        label = m.group("base")
    return _LABEL_SPECS[label], sparsity


@dataclass(frozen=True)
class QuantPolicy:
    """Maps brick-name patterns to quantization labels.

    ``rules`` are (regex, label) pairs matched against pytree key-paths or
    brick names, first match wins.  The paper's configurations, e.g.
    ``em-fp16 | vis-fp16 | dec-q4f16``, are expressed as profiles below.
    """

    name: str
    rules: Tuple[Tuple[str, str], ...]
    min_size: int = 1 << 14      # don't quantize tiny leaves (norms, biases)

    def label_for(self, path: str) -> str:
        for pat, label in self.rules:
            if re.search(pat, path):
                return label
        return "bf16"

    def spec_for(self, path: str) -> Optional[QuantSpec]:
        return parse_label(self.label_for(path))[0]


_LABEL_SPECS["q4f16-g32"] = QuantSpec(4, group_size=32)

PROFILES: Dict[str, QuantPolicy] = {
    # the paper's headline config: FP16 vision, W4A16 decoder (Fig. 6/7)
    "nanomind-default": QuantPolicy("nanomind-default", (
        (r"vis|projector", "fp16"),
        (r"embed", "fp16"),
        (r"layers|dec|lm_head", "q4f16"),
    )),
    # pod-serving variant: group 32 so scale groups align with a 16-way
    # tensor-parallel shard of every assigned d_ff/d_model (EXPERIMENTS.md
    # §Perf, deepseek decode iteration: group 64 straddles the shard
    # boundary at d_ff=22016 and forces a full regather)
    "nanomind-serve": QuantPolicy("nanomind-serve", (
        (r"vis|projector", "fp16"),
        (r"embed", "fp16"),
        (r"layers|dec|lm_head", "q4f16-g32"),
    )),
    # ablations from Fig. 7
    "all-fp16": QuantPolicy("all-fp16", ()),
    "all-q4": QuantPolicy("all-q4", ((r".", "q4f16"),)),
    "vis-q4": QuantPolicy("vis-q4", (
        (r"vis|projector", "q4f16"), (r"embed", "fp16"),
        (r"layers|dec|lm_head", "q4f16"),
    )),
    "dec-q2": QuantPolicy("dec-q2", (
        (r"vis|projector|embed", "fp16"),
        (r"layers|dec|lm_head", "q2f16"),
    )),
    "dec-q8": QuantPolicy("dec-q8", (
        (r"vis|projector|embed", "fp16"),
        (r"layers|dec|lm_head", "q8f16"),
    )),
    # EdgeMM-style activation-aware 50% sparsity stacked under W4A16: the
    # pruned rows re-quantize tighter (zeros shrink group max-abs) and the
    # NPU substrates credit the skipped MACs via SUBSTRATES sparse rows
    "nanomind-sparse": QuantPolicy("nanomind-sparse", (
        (r"vis|projector|embed", "fp16"),
        (r"layers|dec|lm_head", "q4f16-g32-sp50"),
    )),
}


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def quantize_tree(params, policy: QuantPolicy, act_scales=None):
    """Quantize (and optionally prune) eligible leaves per the policy.

    ``act_scales`` maps path substrings to per-input-feature activation
    magnitudes for :func:`prune_weights`; leaves whose label carries an
    ``-sp<pct>`` suffix are pruned before quantization (magnitude-only when
    no activation statistics match)."""
    def visit(path, leaf):
        if not isinstance(leaf, jnp.ndarray) or leaf.ndim < 2:
            return leaf
        if leaf.size < policy.min_size:
            return leaf
        if not jnp.issubdtype(leaf.dtype, jnp.floating):
            return leaf
        p = _path_str(path)
        spec, sparsity = parse_label(policy.label_for(p))
        if sparsity > 0.0:
            act = None
            if act_scales:
                for pat, scale in act_scales.items():
                    if pat in p:
                        act = scale
                        break
            leaf = prune_weights(leaf, sparsity, act)
        if spec is None:
            return leaf
        return quantize(leaf, spec)

    return jax.tree_util.tree_map_with_path(visit, params)


def dequantize_tree(params):
    """Inverse of :func:`quantize_tree`; inside jit XLA fuses the dequant
    into each consumer (W4A16 in-register unpack)."""
    return jax.tree.map(
        lambda l: dequantize(l) if isinstance(l, QTensor) else l,
        params, is_leaf=lambda l: isinstance(l, QTensor))


def tree_bytes(params) -> int:
    """Weight bytes after quantization (Fig. 5 memory accounting)."""
    total = 0
    for leaf in jax.tree.leaves(
            params, is_leaf=lambda l: isinstance(l, QTensor)):
        if isinstance(leaf, QTensor):
            total += leaf.nbytes
        elif hasattr(leaf, "nbytes"):
            total += int(leaf.nbytes)
        elif hasattr(leaf, "size") and hasattr(leaf, "dtype"):
            total += int(leaf.size) * jnp.dtype(leaf.dtype).itemsize
    return total
