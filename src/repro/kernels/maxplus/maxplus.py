"""Tropical (max, +) blocked matmul — the paper's rank/critical-path hot-spot
as a TPU kernel.

Longest-path distances over a DAG satisfy D' = D ⊗ A in the (max, +)
semiring; iterating (or squaring) the closure gives ranks / critical paths
for *batches* of task graphs at once (the serving dispatcher plans many small
request DAGs per tick).  On TPU we evaluate ⊗ as a VPU-tiled blocked kernel:
each grid step loads (bm x bk) and (bk x bn) VMEM tiles, forms the
broadcast sum (bm x bk x bn), and max-reduces over k — accumulating the
running maximum in the output tile across the sequential k grid axis.

Tiles default to (128, 128, 128): lane-dim multiples of 128 keep loads
aligned; the fp32 working set (3 tiles + broadcast buffer) stays ~8 MiB,
inside a v5e core's 16 MiB VMEM budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _maxplus_kernel(a_ref, b_ref, o_ref, *, bk: int, nk: int):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, NEG_INF)

    a = a_ref[...]                      # (bm, bk)
    b = b_ref[...]                      # (bk, bn)
    # (bm, bk, bn) broadcast-sum, max-reduce over k — the tropical "matmul"
    s = a[:, :, None] + b[None, :, :]
    o_ref[...] = jnp.maximum(o_ref[...], jnp.max(s, axis=1))


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def maxplus_matmul(a: jnp.ndarray, b: jnp.ndarray, *, bm: int = 128,
                   bn: int = 128, bk: int = 128,
                   interpret: bool | None = None) -> jnp.ndarray:
    """C[i, j] = max_k (A[i, k] + B[k, j]).  a: (m, k); b: (k, n) float32.

    ``interpret`` follows :func:`repro.kernels.resolve_interpret`: compiled
    on a TPU backend, interpreted elsewhere."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, \
        f"dims {(m, k, n)} must tile by {(bm, bk, bn)}"
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        functools.partial(_maxplus_kernel, bk=bk, nk=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(a.astype(jnp.float32), b.astype(jnp.float32))
