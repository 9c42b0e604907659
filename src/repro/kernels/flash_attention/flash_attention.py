"""Causal flash attention (online softmax) — Pallas TPU kernel.

Grid: (batch·kv_heads·q_per_kv, Sq/bq, Sk/bk); the kv axis is the innermost
(sequential) grid dimension, so the running (m, l, acc) statistics live in
VMEM scratch across kv steps — the classic flash decomposition, with block
shapes chosen MXU-aligned (multiples of 128 on the lane dim) and the fp32
working set (q, k, v tiles + acc) ~4 MiB, well under a v5e core's VMEM.

Causality is enforced per (q-block, kv-block) tile; fully-masked tiles write
nothing (the @pl.when guard skips them).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  bq: int, bk: int, nk: int, causal: bool, scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = (not causal) or (ki * bk <= qi * bq + bq - 1)
    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32)            # (bq, d)
        k = k_ref[0].astype(jnp.float32)            # (bk, d)
        v = v_ref[0].astype(jnp.float32)            # (bk, d)
        s = jnp.dot(q, k.T) * scale                 # (bq, bk)
        if causal:
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(p, v)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / (l_ref[...][:, None] + 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention_bhsd(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                         causal: bool = True, bq: int = 256, bk: int = 256,
                         interpret: bool | None = None) -> jnp.ndarray:
    """q: (BH, Sq, D); k, v: (BH, Sk, D) — same head count (pre-broadcast GQA).

    ``interpret`` follows :func:`repro.kernels.resolve_interpret`: compiled
    on a TPU backend, interpreted elsewhere."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    bq = min(bq, sq)
    while sq % bq:          # largest divisor <= requested block
        bq -= 1
    bk = min(bk, sk)
    while sk % bk:
        bk -= 1
    grid = (bh, sq // bq, sk // bk)
    scale = 1.0 / (d ** 0.5)
    return pl.pallas_call(
        functools.partial(_flash_kernel, bq=bq, bk=bk, nk=grid[2],
                          causal=causal, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
