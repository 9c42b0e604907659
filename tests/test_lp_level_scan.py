"""The device LP's longest-path scans step over the DAG's topological
levels.  The per-task scans they replaced live on here as the oracle: the
level scans must give the exact longest path bit for bit, and the soft
path and its gradient to float32 rounding."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import hlp_jax
from repro.core.dag import CPU, GPU, TaskGraph
from repro.core.hlp_jax import (PaddedDag, hard_longest_path,
                                soft_longest_path, solve_hlp_jax)
from repro.core.workloads import chameleon

from conftest import random_dag

_NEG = -1e30


# ------------------------------------------------- the per-task oracle scans
def task_soft_longest_path(d, topo, times, tau, edge_delay=None):
    """One scan step per task, in topological order."""

    def step(finish, j):
        pf = finish[d.pred[j]]
        if edge_delay is not None:
            pf = pf + edge_delay[j]
        pf = jnp.where(d.pred_mask[j], pf, _NEG)
        m = jnp.max(pf)
        has_pred = jnp.any(d.pred_mask[j])
        soft = m + tau * jnp.log(jnp.sum(jnp.exp((pf - m) / tau)) + 1e-30)
        start = jnp.where(has_pred, jnp.maximum(soft, 0.0), 0.0)
        return finish.at[j].set(start + times[j]), ()

    finish, _ = jax.lax.scan(step, jnp.zeros_like(times), topo)
    m = jnp.max(finish)
    return m + tau * jnp.log(jnp.sum(jnp.exp((finish - m) / tau)) + 1e-30)


def task_hard_longest_path(d, topo, times, edge_delay=None):
    def step(finish, j):
        pf = finish[d.pred[j]]
        if edge_delay is not None:
            pf = pf + edge_delay[j]
        pf = jnp.where(d.pred_mask[j], pf, 0.0)
        return finish.at[j].set(jnp.max(pf, initial=0.0) + times[j]), ()

    finish, _ = jax.lax.scan(step, jnp.zeros_like(times), topo)
    return jnp.max(finish)


# --------------------------------------------------------------- the graphs
def _chain(n=12):
    rng = np.random.default_rng(5)
    return TaskGraph.build(rng.uniform(0.5, 3.0, (n, 2)),
                           [(i, i + 1) for i in range(n - 1)])


def _edgeless(n=9):
    return TaskGraph.build(np.random.default_rng(6).uniform(0.5, 3.0, (n, 2)),
                           [])


GRAPHS = {
    "potrf4": lambda: chameleon("potrf", 4, 512),
    "potrf10": lambda: chameleon("potrf", 10, 512),
    "getrf4": lambda: chameleon("getrf", 4, 512),
    "getrf10": lambda: chameleon("getrf", 10, 512),
    "random0": lambda: random_dag(0, n=40, p_edge=0.1),
    "random1": lambda: random_dag(1, n=25, p_edge=0.3),
    "random2": lambda: random_dag(2),
    "chain": _chain,
    "edgeless": _edgeless,
    "single": lambda: TaskGraph.build(np.array([[2.0, 1.0]]), []),
}


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


@pytest.mark.parametrize("delay", [False, True], ids=["plain", "edge_delay"])
@pytest.mark.parametrize("name", list(GRAPHS))
def test_level_scans_match_task_scans(name, delay):
    g = GRAPHS[name]()
    d = PaddedDag.from_graph(g)
    topo = jnp.asarray(g.topo)
    for j in range(g.n):                       # the vectorised fill
        k = g.preds(j).size
        np.testing.assert_array_equal(d.pred[j, :k], g.preds(j))
        assert not np.asarray(d.pred_mask[j, k:]).any()
        np.testing.assert_array_equal(d.pred_comm[j, :k],
                                      g.comm[g.pred_edges(j)])
    L, W = d.levels.shape
    levels = np.asarray(d.levels)
    assert L == int(g.level.max()) + 1
    assert W == int(np.bincount(g.level).max())
    for lvl in range(L):
        np.testing.assert_array_equal(
            np.sort(levels[lvl][levels[lvl] < g.n]),
            np.flatnonzero(g.level == lvl))
    rng = np.random.default_rng(17)
    x = jnp.asarray(rng.random(g.n), jnp.float32)
    times = d.pc * x + d.pg * (1.0 - x)
    ed = (jnp.asarray(rng.uniform(0.0, 2.0, d.pred.shape), jnp.float32)
          if delay else None)
    tau = jnp.float32(0.05) * jnp.max(times)

    hard = jax.jit(hard_longest_path)(d, times, ed)
    want = jax.jit(task_hard_longest_path)(d, topo, times, ed)
    assert float(hard) == float(want)          # bit-identical

    soft = jax.jit(soft_longest_path)(d, times, tau, ed)
    want = jax.jit(task_soft_longest_path)(d, topo, times, tau, ed)
    assert _rel(soft, want) <= 1e-6

    argnums = (0, 1) if delay else 0
    grad = jax.jit(jax.grad(lambda t, e: soft_longest_path(d, t, tau, e),
                            argnums=argnums))(times, ed)
    want = jax.jit(jax.grad(
        lambda t, e: task_soft_longest_path(d, topo, t, tau, e),
        argnums=argnums))(times, ed)
    for a, b in zip(jax.tree_util.tree_leaves(grad),
                    jax.tree_util.tree_leaves(want)):
        assert _rel(a, b) <= 1e-6


def test_level_scan_solve_gives_the_task_scan_allocation(monkeypatch):
    """A whole device-LP solve, potrf nb = 10 on (64, 8) at the adapter's
    300 iterations, allocates as the per-task scans did.  The level scan
    sums the soft path's gradient in another order, so on other (graph,
    platform) pairs Adam may end at another near-optimal iterate."""
    g = chameleon("potrf", 10, 512)
    m, k, iters = 64, 8, 300
    sol = solve_hlp_jax(g, m, k, iters=iters)

    topo = jnp.asarray(g.topo)
    monkeypatch.setattr(
        hlp_jax, "soft_longest_path",
        lambda d, t, tau, e=None: task_soft_longest_path(d, topo, t, tau, e))
    monkeypatch.setattr(
        hlp_jax, "hard_longest_path",
        lambda d, t, e=None: task_hard_longest_path(d, topo, t, e))
    oracle = jax.jit(hlp_jax._solve.__wrapped__,
                     static_argnames=("m", "k", "iters"))
    x, _ = oracle(PaddedDag.from_graph(g), m, k, iters, 0)
    x = np.asarray(x, np.float64)
    np.testing.assert_array_equal(sol.alloc,
                                  np.where(x >= 0.5, CPU, GPU).astype(np.int32))
    np.testing.assert_allclose(sol.x_frac, x, rtol=0, atol=1e-6)
    assert sol.lp_value == pytest.approx(g.lp_objective([m, k], x), rel=1e-6)


def test_device_solve_span_carries_the_level_shape():
    g = chameleon("getrf", 4, 512)
    with obs.capture():
        solve_hlp_jax(g, 16, 2, iters=5)
        (ev,) = [e for e in obs.wall_events()
                 if e["name"] == "lp.device_solve"]
    counts = np.bincount(g.level)
    assert ev["args"] == {"n": g.n, "iters": 5, "levels": counts.size,
                          "width": int(counts.max())}
