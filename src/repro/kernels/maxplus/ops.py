"""jit'd wrappers: batched tropical closure for DAG rank / critical path.

The serving dispatcher (repro.serve.dispatch) plans many small request DAGs
per scheduling tick; ranks for all of them are computed in one batched
closure: log2(n) tropical squarings of the padded adjacency, evaluated by the
Pallas kernel (vmapped over the batch).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .maxplus import NEG_INF, maxplus_matmul
from .ref import maxplus_matmul_ref


def dense_adjacency(n: int, edges, pad_to: int = 128) -> np.ndarray:
    """(p, p) float32 matrix: 0.0 on edges, NEG_INF elsewhere (p = padded n)."""
    p = max(pad_to, int(np.ceil(n / pad_to)) * pad_to)
    adj = np.full((p, p), NEG_INF, dtype=np.float32)
    for i, j in edges:
        adj[i, j] = 0.0
    return adj


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def longest_path_closure(adj: jnp.ndarray, times: jnp.ndarray,
                         use_pallas: bool = True) -> jnp.ndarray:
    """Finish times for every task of a dense-adjacency DAG.

    adj: (p, p) with 0.0 edges / NEG_INF; times: (p,) processing times
    (padding rows must carry times = 0).  O(p³ log p) tropical closure —
    profitable for batches of small graphs, not one huge sparse graph.
    """
    p = adj.shape[0]
    mm = maxplus_matmul if use_pallas else maxplus_matmul_ref
    # W[i,j] = times[i] + adj[i,j]: edge-weighted by the source's duration.
    w = times[:, None] + adj
    # closure by repeated squaring of (I_tropical ⊕ W)
    eye = jnp.where(jnp.eye(p, dtype=bool), 0.0, NEG_INF).astype(jnp.float32)
    c = jnp.maximum(eye, w)
    for _ in range(int(np.ceil(np.log2(max(p, 2))))):
        c = mm(c, c)
    # longest incoming path weight + own time
    best_in = jnp.max(c, axis=0)
    return jnp.maximum(times, best_in + times)


def batched_ranks(adjs: jnp.ndarray, times: jnp.ndarray,
                  use_pallas: bool = True):
    """Upward ranks for a batch of DAGs: rank = longest path to any sink,
    computed on the reversed graphs.  adjs: (B, p, p); times: (B, p)."""
    rev = jnp.swapaxes(adjs, -1, -2)
    fn = functools.partial(longest_path_closure, use_pallas=use_pallas)
    return jax.vmap(fn)(rev, times)
