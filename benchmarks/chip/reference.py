"""The plain reference the benchmark holds the program to.

It imports nothing of the program.  From a run it takes the inputs the
benchmark generated (task times, edges, transfer costs, seeds) and the
answers under test (plans, makespans, committed schedules), and computes
what those answers should be:

* ``realized_times`` — a plan's per-task times under lognormal
  misprediction: row ``s`` multiplies every task's estimate by one
  ``LogNormal(-scale²/2, scale)`` draw of ``numpy.random.default_rng(s)``.
* ``replay`` — a static plan's makespan under those times: each task
  starts when its DAG predecessors have finished (plus the transfer cost
  of an edge whose ends sit on different resource types), when the task
  before it on its processor has finished, and no earlier than its floor.
  Arithmetic is float64, or the lower ``dtype`` of the control.
* ``plan_faults`` — what makes a plan no plan: a task on no processor or
  on two, a processor that does not exist, a processor order that
  contradicts the DAG.
* ``hlp_lambda`` — the optimum λ of the paper's allocation LP (HLP, §3)
  on a CPU + GPU platform, or, given an allocation, the least λ of an LP
  solution that rounds to it (CPU share at least 1/2 on its CPU tasks, at
  most 1/2 on its GPU tasks).  An allocation rounded from an optimal LP
  solution reaches the optimum; one rounded from a poor solution does not.
* ``ols_makespan`` — the paper's scheduling phase (§4.1): list scheduling
  of a fixed allocation, the ready task of highest upward rank first.
* ``heft_makespan`` — insertion-based HEFT over typed processor pools,
  ties toward the GPU pool and then the lower processor.

The last three follow the paper's model, in which edges carry no
transfer cost.
"""
from __future__ import annotations

import heapq

import numpy as np


def realized_times(proc: np.ndarray, alloc: np.ndarray, seeds,
                   scale: float) -> np.ndarray:
    """(len(seeds), n) times of the plan's allocation; ``None`` in
    ``seeds`` gives the estimate itself (the clean row)."""
    n = proc.shape[0]
    base = proc[np.arange(n), np.asarray(alloc, dtype=np.int64)]
    rows = []
    for s in seeds:
        if s is None:
            rows.append(base)
        else:
            mult = np.random.default_rng(int(s)).lognormal(
                -0.5 * scale ** 2, scale, size=n)
            rows.append(base * mult)
    return np.stack(rows)


def _preds(n: int, edges: np.ndarray, comm: np.ndarray, alloc: np.ndarray,
           sequences) -> tuple[list, list]:
    """Per task: predecessor ids and the delay each edge adds."""
    preds: list[list[int]] = [[] for _ in range(n)]
    delays: list[list[float]] = [[] for _ in range(n)]
    for (a, b), c in zip(np.asarray(edges).reshape(-1, 2), comm):
        a, b = int(a), int(b)
        preds[b].append(a)
        delays[b].append(float(c) if alloc[a] != alloc[b] else 0.0)
    for seq in sequences:
        for a, b in zip(seq[:-1], seq[1:]):
            preds[int(b)].append(int(a))
            delays[int(b)].append(0.0)
    return preds, delays


def _topo(n: int, preds: list) -> list[int] | None:
    """A topological order of the graph given by ``preds``, or ``None``
    when it has a cycle."""
    succs: list[list[int]] = [[] for _ in range(n)]
    indeg = [len(p) for p in preds]
    for j, pj in enumerate(preds):
        for i in pj:
            succs[i].append(j)
    order = [j for j in range(n) if indeg[j] == 0]
    head = 0
    while head < len(order):
        u = order[head]
        head += 1
        for v in succs[u]:
            indeg[v] -= 1
            if indeg[v] == 0:
                order.append(v)
    return order if len(order) == n else None


def replay(n: int, edges: np.ndarray, comm: np.ndarray, alloc: np.ndarray,
           sequences, times: np.ndarray, floor: np.ndarray | None = None,
           dtype=np.float64) -> np.ndarray:
    """(R,) makespans of one static plan under each row of ``times``.

    ``sequences`` lists each processor's tasks in order.  ``dtype`` is the
    precision of every operation (the control passes a lower one)."""
    alloc = np.asarray(alloc, dtype=np.int64)
    preds, delays = _preds(n, edges, comm, alloc, sequences)
    order = _topo(n, preds)
    if order is None:
        raise ValueError("the plan's processor order contradicts its DAG")
    t = np.asarray(times, dtype=np.float64).astype(dtype)
    R = t.shape[0]
    zero = np.zeros(R, dtype=dtype)
    fl = (np.zeros(n) if floor is None else np.asarray(floor)).astype(dtype)
    finish = np.zeros((n, R), dtype=dtype)
    for j in order:
        start = zero
        if preds[j]:
            d = np.asarray(delays[j], dtype=np.float64).astype(dtype)
            start = np.max(finish[preds[j]] + d[:, None], axis=0)
            start = np.maximum(start, zero)
        start = np.maximum(start, fl[j])
        finish[j] = start + t[:, j]
    return finish.max(axis=0).astype(np.float64)


def rel_err(got, want) -> float:
    """Largest relative gap between two arrays (0 for empty ones)."""
    got = np.asarray(got, dtype=np.float64)
    want = np.asarray(want, dtype=np.float64)
    if not want.size:
        return 0.0
    return float(np.max(np.abs(got - want)
                        / np.maximum(np.abs(want), 1e-300)))


def plan_faults(n: int, edges: np.ndarray, counts, alloc: np.ndarray,
                sequences: dict) -> int:
    """Number of faults that make ``(alloc, sequences)`` no plan for an
    n-task DAG on pools of ``counts`` processors (rigid tasks).

    ``sequences`` maps ``(type, processor)`` to that processor's tasks."""
    faults = 0
    alloc = np.asarray(alloc, dtype=np.int64)
    if alloc.shape != (n,) or alloc.min(initial=0) < 0 \
            or alloc.max(initial=0) >= len(counts):
        return 1
    seen = np.zeros(n, dtype=np.int64)
    for (q, pid), seq in sequences.items():
        if not 0 <= pid < counts[q]:
            faults += 1
        for j in seq:
            seen[j] += 1
            if alloc[j] != q:
                faults += 1
    faults += int(np.sum(seen != 1))
    preds, _ = _preds(n, edges, np.zeros(len(edges)), alloc,
                      list(sequences.values()))
    if _topo(n, preds) is None:
        faults += 1
    return faults


def _upward_rank(n: int, edges: np.ndarray, times: np.ndarray) -> np.ndarray:
    """rank(j) = times[j] + the largest rank among j's successors."""
    succs: list[list[int]] = [[] for _ in range(n)]
    preds: list[list[int]] = [[] for _ in range(n)]
    for a, b in np.asarray(edges).reshape(-1, 2):
        succs[int(a)].append(int(b))
        preds[int(b)].append(int(a))
    rank = np.zeros(n)
    for u in reversed(_topo(n, preds)):
        best = max((rank[v] for v in succs[u]), default=0.0)
        rank[u] = times[u] + best
    return rank


def hlp_lambda(edges: np.ndarray, proc: np.ndarray, counts,
               alloc: np.ndarray | None = None) -> float:
    """λ of HLP on ``counts = (m CPUs, k GPUs)``: minimize λ over CPU
    shares x in [0, 1] and completion times C, where task j takes
    ``x·proc[j, 0] + (1 − x)·proc[j, 1]``, starts after its predecessors'
    C, ends by its own C ≤ λ, and each pool's load over its size is at
    most λ.  With ``alloc`` (0 = CPU, 1 = GPU) each share is held to the
    side the allocation rounds it to."""
    from scipy.optimize import linprog
    from scipy.sparse import coo_matrix

    n = proc.shape[0]
    m, k = (float(c) for c in counts)
    pc, pg = proc[:, 0].astype(np.float64), proc[:, 1].astype(np.float64)
    d = pc - pg
    X, C, LAM = 0, n, 2 * n                     # variable offsets
    rows, cols, vals, rhs = [], [], [], []

    def row(entries, b):
        r = len(rhs)
        for c, v in entries:
            rows.append(r)
            cols.append(c)
            vals.append(v)
        rhs.append(b)

    for a, b in np.asarray(edges).reshape(-1, 2):
        a, b = int(a), int(b)                   # C_a + len_b <= C_b
        row([(C + a, 1.0), (C + b, -1.0), (X + b, d[b])], -pg[b])
    for j in range(n):
        row([(X + j, d[j]), (C + j, -1.0)], -pg[j])          # len_j <= C_j
        row([(C + j, 1.0), (LAM, -1.0)], 0.0)                # C_j <= λ
    row([(X + j, pc[j]) for j in range(n)] + [(LAM, -m)], 0.0)
    row([(X + j, -pg[j]) for j in range(n)] + [(LAM, -k)], -pg.sum())
    A = coo_matrix((vals, (rows, cols)), shape=(len(rhs), 2 * n + 1)).tocsr()
    if alloc is None:
        xb = [(0.0, 1.0)] * n
    else:
        xb = [(0.5, 1.0) if int(q) == 0 else (0.0, 0.5) for q in alloc]
    cost = np.zeros(2 * n + 1)
    cost[LAM] = 1.0
    res = linprog(cost, A_ub=A, b_ub=np.asarray(rhs),
                  bounds=xb + [(0.0, None)] * (n + 1), method="highs")
    if not res.success:
        raise RuntimeError(f"reference LP failed: {res.message}")
    return float(res.fun)


def lp_gap(edges: np.ndarray, proc: np.ndarray, counts,
           alloc: np.ndarray, optimum: float | None = None) -> float:
    """How far the best LP solution that rounds to ``alloc`` lies above
    the LP optimum, as a share of the optimum (0 for an allocation
    rounded from an optimal solution)."""
    best = hlp_lambda(edges, proc, counts) if optimum is None else optimum
    return hlp_lambda(edges, proc, counts, alloc) / best - 1.0


def ols_makespan(edges: np.ndarray, proc: np.ndarray, counts,
                 alloc: np.ndarray) -> float:
    """Makespan of list scheduling ``alloc``: whenever a processor of a
    pool is free, it starts the ready task of that pool with the highest
    upward rank under the allocated times (the lower task id on a tie)."""
    n = proc.shape[0]
    alloc = np.asarray(alloc, dtype=np.int64)
    times = proc[np.arange(n), alloc].astype(np.float64)
    rank = _upward_rank(n, edges, times)
    succs: list[list[int]] = [[] for _ in range(n)]
    indeg = np.zeros(n, dtype=np.int64)
    for a, b in np.asarray(edges).reshape(-1, 2):
        succs[int(a)].append(int(b))
        indeg[int(b)] += 1
    Q = len(counts)
    free = [[0.0] * int(counts[q]) for q in range(Q)]   # per-processor
    waiting = [[] for _ in range(Q)]    # (ready time, -rank, id)
    ready = [[] for _ in range(Q)]      # (-rank, id)
    for j in np.flatnonzero(indeg == 0):
        heapq.heappush(waiting[alloc[j]], (0.0, -rank[j], int(j)))
    ready_at = np.zeros(n)
    finish = np.zeros(n)
    t, done, eps = 0.0, 0, 1e-15
    while done < n:
        moved = True
        while moved:
            moved = False
            for q in range(Q):
                while waiting[q] and waiting[q][0][0] <= t + eps:
                    _, r, j = heapq.heappop(waiting[q])
                    heapq.heappush(ready[q], (r, j))
                while ready[q] and min(free[q]) <= t + eps:
                    _, j = heapq.heappop(ready[q])
                    p = min(range(len(free[q])), key=free[q].__getitem__)
                    finish[j] = t + times[j]
                    free[q][p] = finish[j]
                    done += 1
                    moved = True
                    for v in succs[j]:
                        ready_at[v] = max(ready_at[v], finish[j])
                        indeg[v] -= 1
                        if indeg[v] == 0:
                            heapq.heappush(waiting[alloc[v]],
                                           (ready_at[v], -rank[v], v))
        if done == n:
            break
        nxt = min([min(free[q]) for q in range(Q) if ready[q]]
                  + [waiting[q][0][0] for q in range(Q) if waiting[q]],
                  default=np.inf)
        if not nxt > t:
            raise ValueError("list scheduling stalled")
        t = nxt
    return float(finish.max())


def heft_makespan(edges: np.ndarray, proc: np.ndarray, counts,
                  insertion: bool = True) -> float:
    """Makespan of HEFT: tasks in decreasing upward rank under their
    pool-size-weighted mean time, each placed on the processor where it
    finishes first — in the earliest idle gap that holds it, or after the
    processor's last task with ``insertion=False``."""
    n, Q = proc.shape
    counts = [int(c) for c in counts]
    mean = (proc * np.asarray(counts, dtype=np.float64)).sum(axis=1) \
        / float(sum(counts))
    rank = _upward_rank(n, edges, mean)
    preds: list[list[int]] = [[] for _ in range(n)]
    for a, b in np.asarray(edges).reshape(-1, 2):
        preds[int(b)].append(int(a))
    busy = [[[] for _ in range(counts[q])] for q in range(Q)]
    finish = np.zeros(n)

    def fit(spans, r, p):
        if not insertion:
            return max(r, spans[-1][1]) if spans else r
        end = 0.0
        for s, f in spans:
            if max(r, end) + p <= s + 1e-12:
                return max(r, end)
            end = f
        return max(r, end)

    for j in np.argsort(-rank, kind="stable"):
        r = max((finish[i] for i in preds[j]), default=0.0)
        best = (np.inf, 0, 0, 0.0)
        for q in range(Q):
            for pid in range(counts[q]):
                s = fit(busy[q][pid], r, proc[j, q])
                f = s + proc[j, q]
                if f < best[0] - 1e-12 or (abs(f - best[0]) <= 1e-12
                                           and q > best[1]):
                    best = (f, q, pid, s)
        f, q, pid, s = best
        finish[j] = f
        busy[q][pid].append((s, f))
        busy[q][pid].sort()
    return float(finish.max())
