"""Plain reference of a Qwen2 decoder behind a two-layer GELU projector
(llava-onevision-qwen2, Qwen2-VL), in float32 ``jax.numpy``.

Independent of the code under test: it imports nothing from ``src/``.  It
follows the published description (Qwen2: pre-RMSNorm, GQA attention with
q/k/v biases and rotary positions, SwiGLU MLP; LLaVA: ``Linear -> GELU ->
Linear`` projector whose outputs take the place of the image placeholder
tokens).  Departures, each forced by what the served program takes as
input, are listed in PERF.md: stub patch features stand in for the vision
tower, and M-RoPE is given the position ids the engine feeds.

It also makes the weights both sides use (:func:`make_params`), from a
seed, in one jitted call and in the tree layout the served program reads
(the harness checks the layout against the program's abstract tree).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _ein(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

# fan-in axis count of each matrix leaf, by its name in the tree
_FAN_IN = {"wq": 1, "wk": 1, "wv": 1, "wo": 2, "w_up": 1, "w_gate": 1,
           "w_down": 1, "w1": 1, "w2": 1, "lm_head": 1}


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", getattr(path[-1], "idx", "")))


def _in_layers(path) -> bool:
    return any(getattr(k, "key", None) == "layers" for k in path)


def make_params(key, template):
    """Random weights shaped like ``template`` (a tree of
    ``ShapeDtypeStruct``), drawn leaf by leaf from ``key`` by the leaf's
    name: matrices N(0, 1/fan_in), embedding N(0, 0.02^2), norm scales
    1 + N(0, 0.1^2), biases N(0, 0.1^2).  Leaves under ``layers`` carry a
    leading layer axis, and take their fan-in past it."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(template)
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, (path, sds) in zip(keys, leaves):
        name = _leaf_name(path)
        shape = tuple(sds.shape)
        x = jax.random.normal(k, shape, jnp.float32)
        if name == "embed":
            x = x * 0.02
        elif name == "scale":
            x = 1.0 + 0.1 * x
        elif name in ("bq", "bk", "bv"):
            x = 0.1 * x
        elif name in _FAN_IN:
            lead = 1 if _in_layers(path) else 0
            fan = math.prod(shape[lead:lead + _FAN_IN[name]])
            x = x / math.sqrt(fan)
        else:
            raise KeyError(f"no weight rule for leaf {name!r} at {path}")
        out.append(x.astype(sds.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# weight precision of the control
# ---------------------------------------------------------------------------

def fake_quant(w, mode: str, n_in: int = 1):
    """``w`` in float32 after a round trip through ``mode``'s weight
    format, with one scale per output channel: the leading ``n_in`` axes
    are the input, the rest the output.  ``f32`` leaves it unchanged."""
    w = w.astype(jnp.float32)
    if mode == "f32":
        return w
    red = tuple(range(n_in))
    amax = jnp.max(jnp.abs(w), axis=red, keepdims=True)
    if mode == "int8":
        s = jnp.maximum(amax, 1e-30) / 127.0
        return jnp.clip(jnp.round(w / s), -127, 127) * s
    if mode == "fp8":
        s = jnp.maximum(amax, 1e-30) / 448.0
        return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(f"unknown weight mode {mode!r}")


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def rope_angles(positions3, head_dim: int, theta: float, sections):
    """(S, head_dim/2) rotary angles.  ``positions3`` is (3, S): the
    temporal, height and width position ids of M-RoPE; ``sections`` splits
    the head_dim/2 frequencies among them (plain RoPE: one section)."""
    half = head_dim // 2
    inv = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32)
                           * 2.0 / head_dim))
    parts, off = [], 0
    for i, n in enumerate(sections):
        parts.append(positions3[i].astype(jnp.float32)[:, None]
                     * inv[off:off + n])
        off += n
    return jnp.concatenate(parts, axis=-1)


def rotate(x, ang):
    """Rotate-half RoPE: x (S, H, hd), ang (S, hd/2)."""
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def layer(x, p, ang, mask, sizes, mode):
    """One decoder layer, float32.  x (S, D)."""
    eps = float(sizes["rms_norm_eps"])
    H = int(sizes["num_attention_heads"])
    KV = int(sizes["num_key_value_heads"])
    G = H // KV
    mix, ffn = p["mixer"], p["ffn"]
    h = rms_norm(x, p["norm1"]["scale"], eps)
    q = _ein("sd,dhk->shk", h, fake_quant(mix["wq"], mode, 1)) \
        + mix["bq"].astype(jnp.float32)
    k = _ein("sd,dhk->shk", h, fake_quant(mix["wk"], mode, 1)) \
        + mix["bk"].astype(jnp.float32)
    v = _ein("sd,dhk->shk", h, fake_quant(mix["wv"], mode, 1)) \
        + mix["bv"].astype(jnp.float32)
    q, k = rotate(q, ang), rotate(k, ang)
    hd = q.shape[-1]
    # head j of the queries reads key/value head j // G
    k = jnp.repeat(k, G, axis=1)
    v = jnp.repeat(v, G, axis=1)
    s = _ein("ihk,jhk->hij", q, k) / math.sqrt(hd)
    s = jnp.where(mask[None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = _ein("hij,jhk->ihk", a, v)
    x = x + _ein("shk,hkd->sd", o, fake_quant(mix["wo"], mode, 2))
    h = rms_norm(x, p["norm2"]["scale"], eps)
    gate = _ein("sd,df->sf", h, fake_quant(ffn["w_gate"], mode, 1))
    up = _ein("sd,df->sf", h, fake_quant(ffn["w_up"], mode, 1))
    return x + _ein("sf,fd->sd", jax.nn.silu(gate) * up,
                    fake_quant(ffn["w_down"], mode, 1))


def forward(params, sizes: dict, tokens, feats, positions3, start, *,
            n_out: int, mode: str = "f32"):
    """Logits (n_out, vocab_size) at positions ``start .. start+n_out-1``
    of one sequence.

    tokens (S,) int32, the first ``feats.shape[0]`` of them image
    placeholders whose embeddings the projected ``feats`` replace;
    positions3 (3, S) position ids.  Causal, so positions past the true
    end (padding) change nothing before it.  ``mode`` sets the weight
    format (``f32``, or the control's ``int8`` / ``fp8``)."""
    S = tokens.shape[0]
    D = int(sizes["hidden_size"])
    V = int(sizes["vocab_size"])
    emb = fake_quant(params["embed"].T, mode, 1).T
    x = emb[tokens]
    if feats is not None:
        vp = params["vis_proj"]
        z = _ein("nf,fd->nd", feats.astype(jnp.float32),
                 fake_quant(vp["w1"], mode, 1))
        z = jax.nn.gelu(z, approximate=False)
        z = _ein("nd,de->ne", z, fake_quant(vp["w2"], mode, 1))
        x = jnp.concatenate([z, x[feats.shape[0]:]], axis=0)
    hd = D // int(sizes["num_attention_heads"]) \
        if not sizes.get("head_dim") else int(sizes["head_dim"])
    sections = sizes.get("mrope_section") or [hd // 2]
    ang = rope_angles(positions3, hd, float(sizes["rope_theta"]), sections)
    mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]

    def body(x, p):
        return layer(x, p, ang, mask, sizes, mode), None

    x, _ = jax.lax.scan(body, x, params["layers"][0])
    x = jax.lax.dynamic_slice_in_dim(x, start, n_out, axis=0)
    x = rms_norm(x, params["final_norm"]["scale"],
                 float(sizes["rms_norm_eps"]))
    if sizes.get("tie_word_embeddings"):
        logits = _ein("sd,vd->sv", x, emb)
    else:
        logits = _ein("sd,dv->sv", x,
                      fake_quant(params["lm_head"], mode, 1))
    return logits[:, :V]


def positions(n: int, sizes: dict) -> jnp.ndarray:
    """(3, n) position ids as the served engine assigns them: 0..n-1 on
    every stream, image placeholders included (see PERF.md)."""
    p = jnp.arange(n, dtype=jnp.int32)
    return jnp.stack([p, p, p])


def check_sizes(sizes: dict, program: dict) -> Optional[str]:
    """None when the program's sizes (``program``: the repo config's
    fields) agree with the configuration file's, else what differs."""
    want = {"hidden_size": "d_model", "intermediate_size": "d_ff",
            "num_attention_heads": "n_heads",
            "num_key_value_heads": "n_kv_heads",
            "num_hidden_layers": "n_layers", "vocab_size": "vocab_size",
            "rope_theta": "rope_theta",
            "tie_word_embeddings": "tie_embeddings",
            "mm_hidden_size": "vision_feat_dim"}
    bad = [f"{k}: file {sizes[k]} program {program[v]}"
           for k, v in want.items() if k in sizes and sizes[k] != program[v]]
    return "; ".join(bad) or None
