"""JAX-native HLP solver — the paper's LP as a jitted saddle-free descent.

The HLP relaxation is equivalent to the box-constrained convex program

    min_{x ∈ [0,1]^n}  f(x) = max( CP(x), load_CPU(x)/m, load_GPU(x)/k )

where CP(x) is the DAG longest path under fractional lengths
ℓ_j(x) = p̄_j x_j + p_j (1 - x_j) (a max of linear functions of x, hence
convex), and the loads are linear.  We minimize f with Adam on logits
(x = σ(z)), using a temperature-annealed soft longest path for gradient flow
and tracking the best *exact* iterate.  Everything — including the longest
path, expressed as a ``lax.scan`` over the DAG's topological levels, one
step per level — runs jitted, so the allocation phase scales to graphs far
beyond what the paper solved with GLPK (and runs on accelerators).

Problem data comes from the shared ``repro.core.allocation.AllocationProblem``
IR — the same (type, width) choice grid, per-choice times, area terms and
per-edge comm terms the exact HiGHS backend assembles its LPs from.  One
jitted kernel (``_solve_choice``) serves every choice-grid problem — QHLP,
moldable MHLP, and their comm-aware variants: a per-task softmax over the
grid, with the *expected* transfer cost of each edge under the softmax
distribution (a smooth upper bound on the exact LP's total-variation
crossing term) folded into the soft longest path as comm-augmented edge
delays.  The historical hybrid sigmoid kernel (``_solve``) is kept verbatim
as the comm-free Q=2 fast path — its iterates are pinned bit-for-bit by the
golden suite.

This is a *beyond-paper* substitute for the exact solver in
``repro.core.hlp`` (scipy/HiGHS); the tests validate it against the exact LP
on random instances.  Any iterate x yields λ(x) >= LP*, so ratios reported
against λ(x) are conservative (never flatter than the paper's LP* ratios).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import registry as _obs

from .allocation import AllocationProblem, frac_objective
from .dag import CPU, GPU, TaskGraph
from .hlp import HLPSolution, canonical_round, canonical_round_moldable

_NEG = -1e30


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PaddedDag:
    """Pred-padded DAG and its topological levels in device arrays (static
    shapes for jit)."""
    pred: jnp.ndarray       # (n, P) int32, -1 padded, rows aligned with task ids
    pred_mask: jnp.ndarray  # (n, P) bool
    pc: jnp.ndarray         # (n,) CPU times
    pg: jnp.ndarray         # (n,) GPU times
    pred_comm: jnp.ndarray  # (n, P) transfer cost of each pred edge (0 padded)
    levels: jnp.ndarray     # (L, W) int32: row l holds the tasks of level l,
    #                         padded with n

    @staticmethod
    def from_graph(g: TaskGraph) -> "PaddedDag":
        n = g.n
        deg = np.diff(g.pred_ptr)
        P = max(1, int(deg.max()) if n else 1)
        row = np.repeat(np.arange(n), deg)
        col = np.arange(g.pred_idx.size) - np.repeat(g.pred_ptr[:-1], deg)
        pred = np.full((n, P), -1, dtype=np.int32)
        pred[row, col] = g.pred_idx
        pcomm = np.zeros((n, P), dtype=np.float64)
        pcomm[row, col] = g.comm[g.pred_eid]
        count = np.bincount(g.level, minlength=1)
        by_level = np.argsort(g.level, kind="stable")
        lane = np.arange(n) - np.repeat(np.cumsum(count) - count, count)
        levels = np.full((count.size, int(count.max())), n, dtype=np.int32)
        levels[g.level[by_level], lane] = by_level
        return PaddedDag(
            pred=jnp.asarray(pred), pred_mask=jnp.asarray(pred >= 0),
            pc=jnp.asarray(g.proc[:, CPU]), pg=jnp.asarray(g.proc[:, GPU]),
            pred_comm=jnp.asarray(pcomm), levels=jnp.asarray(levels))


def _level_scan(d: PaddedDag, times: jnp.ndarray,
                edge_delay: jnp.ndarray | None, start) -> jnp.ndarray:
    """Finish times, in task order, from one scan step per topological level.

    A level's tasks depend only on earlier levels, so each step finishes a
    whole level at once from already-final predecessor finishes.  The carry
    is level-major, (L, W): task ``levels[l, i]`` finishes in slot (l, i),
    and padding lanes, with no preds and zero time, finish at 0.
    ``start(pf, mask)`` maps the (P, W) predecessor finishes of a level
    (``edge_delay`` added) to its W start times.
    """
    n = times.shape[0]
    L, W = d.levels.shape
    lanes = d.levels.reshape(-1)
    real = lanes < n
    task = jnp.where(real, lanes, 0)
    slot = jnp.zeros(n, jnp.int32).at[lanes].set(
        jnp.arange(L * W, dtype=jnp.int32), mode="drop")

    def by_level(a):                    # (L·W, P) -> (L, P, W)
        return a.reshape(L, W, -1).transpose(0, 2, 1)

    xs = (jnp.arange(L),
          by_level(slot[jnp.maximum(d.pred[task], 0)]),
          by_level(d.pred_mask[task] & real[:, None]),
          jnp.where(real, times[task], 0.0).reshape(L, W),
          None if edge_delay is None else by_level(edge_delay[task]))

    def step(finish, x):
        lvl, pred_slot, mask, t, delay = x
        pf = finish.reshape(-1)[pred_slot]
        if delay is not None:
            pf = pf + delay
        return finish.at[lvl].set(start(pf, mask) + t), ()

    finish, _ = jax.lax.scan(step, jnp.zeros((L, W), times.dtype), xs)
    return finish.reshape(-1)[slot]


def soft_longest_path(d: PaddedDag, times: jnp.ndarray, tau: jnp.ndarray,
                      edge_delay: jnp.ndarray | None = None) -> jnp.ndarray:
    """Temperature-τ softmax-relaxed longest path; τ→0 recovers the exact CP.

    ``edge_delay`` optionally adds an (n, P) per-pred-slot delay (the
    comm-augmented edge delays of the comm-aware solvers); ``None`` traces
    the historical delay-free graph.
    """

    def start(pf, mask):
        pf = jnp.where(mask, pf, _NEG)
        # soft-max over predecessors (upper-bounds the hard max by τ·log P).
        m = jnp.max(pf, axis=0)
        soft = m + tau * jnp.log(jnp.sum(jnp.exp((pf - m) / tau), axis=0)
                                 + 1e-30)
        return jnp.where(jnp.any(mask, axis=0), jnp.maximum(soft, 0.0), 0.0)

    finish = _level_scan(d, times, edge_delay, start)
    m = jnp.max(finish)
    return m + tau * jnp.log(jnp.sum(jnp.exp((finish - m) / tau)) + 1e-30)


def hard_longest_path(d: PaddedDag, times: jnp.ndarray,
                      edge_delay: jnp.ndarray | None = None) -> jnp.ndarray:
    def start(pf, mask):
        return jnp.max(jnp.where(mask, pf, 0.0), axis=0, initial=0.0)

    return jnp.max(_level_scan(d, times, edge_delay, start))


@partial(jax.jit, static_argnames=("m", "k", "iters"))
def _solve(d: PaddedDag, m: int, k: int, iters: int, seed: int):
    n = d.pc.shape[0]

    def lam_exact(x):
        times = d.pc * x + d.pg * (1.0 - x)
        with jax.named_scope("lp_exact_path"):
            cp = hard_longest_path(d, times)
        return jnp.maximum(cp, jnp.maximum(jnp.dot(d.pc, x) / m,
                                           jnp.dot(d.pg, 1.0 - x) / k))

    def loss(z, tau):
        x = jax.nn.sigmoid(z)
        times = d.pc * x + d.pg * (1.0 - x)
        with jax.named_scope("lp_soft_path"):
            cp = soft_longest_path(d, times, tau)
        terms = jnp.stack([cp, jnp.dot(d.pc, x) / m, jnp.dot(d.pg, 1.0 - x) / k])
        mx = jnp.max(terms)
        return mx + tau * jnp.log(jnp.sum(jnp.exp((terms - mx) / tau)))

    grad = jax.grad(loss)
    scale = jnp.maximum(jnp.max(d.pc), jnp.max(d.pg))
    z0 = 0.01 * jax.random.normal(jax.random.PRNGKey(seed), (n,))

    lr, b1, b2, eps = 0.25, 0.9, 0.999, 1e-8

    def body(carry, i):
        z, mu, nu, best_x, best_val = carry
        # Anneal τ from scale/8 down to scale/512 over the run.
        frac = i.astype(jnp.float32) / max(iters - 1, 1)
        tau = scale * jnp.exp(jnp.log(1 / 8.0) * (1 - frac) + jnp.log(1 / 512.0) * frac)
        gz = grad(z, tau)
        mu = b1 * mu + (1 - b1) * gz
        nu = b2 * nu + (1 - b2) * gz * gz
        mh = mu / (1 - b1 ** (i + 1))
        nh = nu / (1 - b2 ** (i + 1))
        z = z - lr * mh / (jnp.sqrt(nh) + eps)
        x = jax.nn.sigmoid(z)
        val = lam_exact(x)
        better = val < best_val
        best_x = jnp.where(better, x, best_x)
        best_val = jnp.where(better, val, best_val)
        return (z, mu, nu, best_x, best_val), ()

    init = (z0, jnp.zeros(n), jnp.zeros(n), jax.nn.sigmoid(z0),
            lam_exact(jax.nn.sigmoid(z0)))
    with jax.named_scope("lp_adam"):
        (z, _, _, best_x, best_val), _ = jax.lax.scan(
            body, init, jnp.arange(iters, dtype=jnp.int32))
    return best_x, best_val


# ----------------------------------------------------- choice-grid problems
@partial(jax.jit, static_argnames=("iters", "use_comm"))
def _solve_choice(d: PaddedDag, p_choice: jnp.ndarray, area: jnp.ndarray,
                  type_mask: jnp.ndarray, inv_counts: jnp.ndarray,
                  iters: int, seed: int, use_comm: bool = False):
    """First-order solver for any choice-grid ``AllocationProblem``: a
    per-task softmax over the (type, width) choices.

    ``p_choice`` (n, C) holds the choice processing times, ``area`` (n, C)
    the width-weighted areas, ``type_mask`` (Q, C) the pool membership of
    each choice and ``inv_counts`` (Q,) the reciprocal pool sizes.  Same
    Adam-on-logits / annealed-soft-longest-path scheme as the hybrid
    solver, with the softmax replacing the sigmoid.  With ``use_comm`` each
    pred edge is delayed by its cost times the *expected* crossing
    probability under the softmax distribution (smooth in z; an upper
    bound on the exact LP's total-variation crossing term), so the
    gradient sees the network; without it the traced graph is exactly the
    historical comm-free one.
    """
    n, C = p_choice.shape

    def mix(z):
        return jax.nn.softmax(z, axis=1)          # (n, C) choice distribution

    def loads(x):
        # (Q,) per-pool area loads: Σ_j Σ_{c∈q} area[j,c]·x[j,c] / m_q
        per_choice = (area * x).sum(axis=0)       # (C,)
        return (type_mask @ per_choice) * inv_counts

    def delays(x):
        # (n, P) expected transfer delay of each pred edge: cost times the
        # chance two independent draws from the endpoints' type marginals
        # differ (masked slots gather garbage but carry zero cost).
        if not use_comm:
            return None
        X = x @ type_mask.T                       # (n, Q) type marginals
        cross = 1.0 - jnp.einsum("npq,nq->np", X[d.pred], X)
        return d.pred_comm * cross

    def lam_exact(x):
        times = (p_choice * x).sum(axis=1)
        cp = hard_longest_path(d, times, delays(x))
        return jnp.maximum(cp, jnp.max(loads(x)))

    def loss(z, tau):
        x = mix(z)
        times = (p_choice * x).sum(axis=1)
        cp = soft_longest_path(d, times, tau, delays(x))
        terms = jnp.concatenate([jnp.stack([cp]), loads(x)])
        mx = jnp.max(terms)
        return mx + tau * jnp.log(jnp.sum(jnp.exp((terms - mx) / tau)))

    grad = jax.grad(loss)
    scale = jnp.max(jnp.where(jnp.isfinite(p_choice), p_choice, 0.0))
    z0 = 0.01 * jax.random.normal(jax.random.PRNGKey(seed), (n, C))

    lr, b1, b2, eps = 0.25, 0.9, 0.999, 1e-8

    def body(carry, i):
        z, mu, nu, best_x, best_val = carry
        frac = i.astype(jnp.float32) / max(iters - 1, 1)
        tau = scale * jnp.exp(jnp.log(1 / 8.0) * (1 - frac)
                              + jnp.log(1 / 512.0) * frac)
        gz = grad(z, tau)
        mu = b1 * mu + (1 - b1) * gz
        nu = b2 * nu + (1 - b2) * gz * gz
        mh = mu / (1 - b1 ** (i + 1))
        nh = nu / (1 - b2 ** (i + 1))
        z = z - lr * mh / (jnp.sqrt(nh) + eps)
        x = mix(z)
        val = lam_exact(x)
        better = val < best_val
        best_x = jnp.where(better, x, best_x)
        best_val = jnp.where(better, val, best_val)
        return (z, mu, nu, best_x, best_val), ()

    init = (z0, jnp.zeros((n, C)), jnp.zeros((n, C)), mix(z0),
            lam_exact(mix(z0)))
    (_, _, _, best_x, best_val), _ = jax.lax.scan(
        body, init, jnp.arange(iters, dtype=jnp.int32))
    return best_x, best_val


def _solve_problem(prob: AllocationProblem, iters: int,
                   seed: int) -> np.ndarray:
    """Run the jitted choice-grid kernel on an ``AllocationProblem`` and
    return the renormalized (n, C) fractional distribution."""
    p_dev = np.where(prob.finite, prob.p_choice, 1e12)  # price out, keep
    #                                                     grads finite
    area = p_dev * prob.width_of.astype(np.float64)
    d = PaddedDag.from_graph(prob.g)
    x, _ = _solve_choice(d, jnp.asarray(p_dev), jnp.asarray(area),
                         jnp.asarray(prob.type_mask),
                         jnp.asarray(1.0 / np.asarray(prob.counts,
                                                      dtype=np.float64)),
                         int(iters), int(seed), use_comm=prob.comm_aware)
    x = np.asarray(x, dtype=np.float64)
    x = np.where(prob.finite, x, 0.0)
    x /= x.sum(axis=1, keepdims=True)
    return x


def solve_mhlp_jax(g: TaskGraph, machine, iters: int = 400, seed: int = 0, *,
                   canonical: bool = False,
                   comm_aware: bool = False) -> HLPSolution:
    """First-order width-indexed MHLP — ``hlp.solve_mhlp``'s jitted sibling.

    Optimizes a per-task softmax over the (type, width) choice grid of the
    shared ``AllocationProblem`` with the annealed soft longest path;
    ``comm_aware=True`` folds each edge's expected transfer cost into the
    path (the gradient then *sees the network*).  As with the hybrid
    solver, the returned ``lp_value`` is the *exact* λ of the best iterate
    — a feasible relaxation objective, hence ≥ the HiGHS optimum (validated
    in the tests), so ratios reported against it stay conservative.
    ``canonical=True`` shares ``canonical_round_moldable`` with the exact
    solver for task-wise comparable decisions.
    """
    from repro.platform import as_platform

    platform = as_platform(machine)
    prob = AllocationProblem.build(g, platform, comm_aware=comm_aware)
    choices, p_choice = prob.choices, prob.p_choice
    x = _solve_problem(prob, iters, seed)
    val = frac_objective(prob, x)
    if canonical:
        alloc, width = canonical_round_moldable(g, platform, x, prob=prob)
    else:
        alloc = np.empty(g.n, dtype=np.int32)
        width = np.empty(g.n, dtype=np.int32)
        for j in range(g.n):
            cand = np.flatnonzero(x[j] >= x[j].max() - 1e-9)
            c = int(cand[np.lexsort((
                [choices[int(cc)][1] for cc in cand], p_choice[j, cand]))[0]])
            alloc[j], width[j] = choices[c]
    return HLPSolution(x_frac=x, lp_value=float(val), alloc=alloc,
                       width=width, status="first-order")


def solve_hlp_jax(g: TaskGraph, m: int, k: int, iters: int = 400,
                  seed: int = 0, *, canonical: bool = False,
                  comm_aware: bool = False) -> HLPSolution:
    """Drop-in replacement for ``hlp.solve_hlp`` (approximate but jitted/scalable).

    ``canonical=True`` routes the rounding through the deterministic
    degeneracy-free tie-break shared with the exact solver
    (``hlp.canonical_round``), making the two allocations comparable
    task-wise even though the fractional optima differ.  ``comm_aware=True``
    solves the rigid Q=2 choice grid through the comm-augmented kernel
    (edge costs enter the soft longest path); the comm-free path is the
    historical sigmoid kernel, bit-for-bit.
    """
    if g.num_types != 2:
        raise ValueError("hybrid solver: Q must be 2")
    prob = AllocationProblem.build(g, (m, k), comm_aware=comm_aware,
                                   rigid=True)
    if prob.comm_aware:
        x2 = _solve_problem(prob, iters, seed)
        x = x2[:, CPU]
        val = frac_objective(prob, x2)
        alloc = (canonical_round(g, m, k, x, prob=prob) if canonical
                 else np.where(x >= 0.5, CPU, GPU).astype(np.int32))
        return HLPSolution(x_frac=x, lp_value=float(val), alloc=alloc,
                           status="first-order")
    d = PaddedDag.from_graph(g)
    L, W = d.levels.shape
    with _obs.span("lp.device_solve", n=g.n, iters=int(iters), levels=L,
                   width=W):
        x, val = _solve(d, int(m), int(k), int(iters), int(seed))
        x = np.asarray(x, dtype=np.float64)   # blocks until the solve ends
    # λ(x) is exact for the returned iterate -> a *feasible* LP objective.
    val = g.lp_objective([m, k], x)
    alloc = (canonical_round(g, m, k, x) if canonical
             else np.where(x >= 0.5, CPU, GPU).astype(np.int32))
    return HLPSolution(x_frac=x, lp_value=float(val), alloc=alloc,
                       status="first-order")
