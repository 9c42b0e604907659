"""jit'd model-facing wrapper: (B, S, H, Dh) GQA layout -> flash kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention_bhsd


@functools.partial(jax.jit, static_argnames=("causal",))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True) -> jnp.ndarray:
    """q: (B, S, H, Dh); k, v: (B, S, Hkv, Dh) with H = G·Hkv (GQA)."""
    b, s, h, dh = q.shape
    hkv = k.shape[2]
    g = h // hkv
    # broadcast kv heads to q heads, fold (B, H) into one grid axis
    kb = jnp.repeat(k, g, axis=2)
    vb = jnp.repeat(v, g, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, dh)
    kf = kb.transpose(0, 2, 1, 3).reshape(b * h, s, dh)
    vf = vb.transpose(0, 2, 1, 3).reshape(b * h, s, dh)
    out = flash_attention_bhsd(qf, kf, vf, causal=causal)
    return out.reshape(b, h, s, dh).transpose(0, 2, 1, 3)
