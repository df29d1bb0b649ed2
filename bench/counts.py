"""Operations and bytes, computed from shapes, for the kernels on the
served path and for the model's work per token.

The kernel counts wait for a roofline reader: the fused decode kernels
carry no stable name in the device trace yet (see PERF.md).

Sizes come from a configuration file's ``config`` block (Hugging Face
names).  Counts are of what the algorithm needs: a multiply-add is two
operations; bytes are each operand read once and each result written
once, at the served dtype's width.  Padding (vocab rows, prompt buckets,
cohort rows beyond the live ones, KV positions past a row's length) is
not work and is never counted.
"""
from __future__ import annotations


def dims(sizes: dict) -> dict:
    D = int(sizes["hidden_size"])
    H = int(sizes["num_attention_heads"])
    hd = int(sizes.get("head_dim") or D // H)
    return dict(D=D, H=H, KV=int(sizes["num_key_value_heads"]), hd=hd,
                F=int(sizes["intermediate_size"]),
                L=int(sizes["num_hidden_layers"]),
                V=int(sizes["vocab_size"]),
                Fv=int(sizes.get("mm_hidden_size") or 0))


def fused_qkv(rows: int, sizes: dict, itemsize: int = 2) -> tuple:
    """(flops, bytes) of one ``fused_qkv_pallas`` call over ``rows``
    cohort rows: three projections with biases."""
    d = dims(sizes)
    cols = (d["H"] + 2 * d["KV"]) * d["hd"]
    flops = 2 * rows * d["D"] * cols + rows * cols
    nbytes = itemsize * (d["D"] * cols + cols + rows * d["D"] + rows * cols)
    return flops, nbytes


def fused_mlp(rows: int, sizes: dict, itemsize: int = 2) -> tuple:
    """(flops, bytes) of one ``fused_mlp_pallas`` call: gate and up
    projections, SwiGLU, down projection."""
    d = dims(sizes)
    flops = 2 * rows * d["D"] * d["F"] * 3 + 4 * rows * d["F"]
    nbytes = itemsize * (3 * d["D"] * d["F"] + 2 * rows * d["D"])
    return flops, nbytes


def linear_flops_per_token(sizes: dict) -> int:
    """Decoder matmul operations per token, head excluded."""
    d = dims(sizes)
    attn = (d["D"] * (d["H"] + 2 * d["KV"]) * d["hd"]
            + d["H"] * d["hd"] * d["D"])
    return 2 * d["L"] * (attn + 3 * d["D"] * d["F"])


def attention_flops(sizes: dict, context: int) -> int:
    """Scores and weighted sum of one query over ``context`` positions,
    every layer."""
    d = dims(sizes)
    return 4 * d["L"] * d["H"] * d["hd"] * context


def head_flops(sizes: dict) -> int:
    d = dims(sizes)
    return 2 * d["D"] * d["V"]


def projector_flops(sizes: dict, n_patches: int) -> int:
    d = dims(sizes)
    return 2 * n_patches * (d["Fv"] * d["D"] + d["D"] * d["D"])


def prefill_flops(sizes: dict, prompt_len: int, n_patches: int) -> int:
    """A prompt's prefill: projector over its patches, every layer over
    every prompt position with causal attention, the head at the last
    position only (as the engine computes it)."""
    S = prompt_len
    causal = sum_context(S)
    return (projector_flops(sizes, n_patches)
            + S * linear_flops_per_token(sizes)
            + attention_flops(sizes, 1) * causal + head_flops(sizes))


def sum_context(S: int) -> int:
    """Positions attended over a causal prompt of length S: 1 + ... + S."""
    return S * (S + 1) // 2


def decode_flops(sizes: dict, context: int) -> int:
    """One decoded token attending ``context`` positions (itself
    included)."""
    return (linear_flops_per_token(sizes) + attention_flops(sizes, context)
            + head_flops(sizes))
