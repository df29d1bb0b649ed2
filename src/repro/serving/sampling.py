"""Token sampling: greedy / temperature / top-k / top-p, pure jnp."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp


def greedy(logits: jnp.ndarray) -> jnp.ndarray:
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


@jax.jit
def sample_rows(logits: jnp.ndarray, key, temps: jnp.ndarray) -> jnp.ndarray:
    """logits (B, V), per-row temperatures (B,) f32 -> tokens (B,) int32.

    A row at temperature 0 takes the argmax; the others draw from
    ``logits / max(t, 1e-4)``, all from the one ``key``."""
    logits = logits.astype(jnp.float32)
    t = jnp.maximum(temps, 1e-4)[:, None]
    drawn = jax.random.categorical(key, logits / t, axis=-1)
    return jnp.where(temps > 0, drawn, greedy(logits)).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("top_k", "top_p"))
def sample(logits: jnp.ndarray, key, *, temperature: float = 1.0,
           top_k: int = 0, top_p: float = 1.0) -> jnp.ndarray:
    """logits (B, V) -> tokens (B,) int32."""
    logits = logits.astype(jnp.float32)
    t = jnp.maximum(jnp.asarray(temperature, jnp.float32), 1e-4)
    logits = logits / t
    if top_k:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, -1e30, logits)
    if top_p < 1.0:
        sorted_l = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_l, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        cutoff_idx = jnp.sum(cum < top_p, axis=-1)
        cutoff = jnp.take_along_axis(sorted_l, cutoff_idx[:, None], axis=-1)
        logits = jnp.where(logits < cutoff, -1e30, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)
