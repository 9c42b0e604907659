"""Vectorized (vmapped) makespan evaluation for static plans.

The replay of a static ``Plan`` under realized runtimes is a longest-path
computation on the *augmented* DAG = precedence edges + processor-sequence
chain edges (see ``engine._execute_plan``), where a precedence edge whose
endpoints sit on different resource types additionally delays its successor
by the edge's transfer cost ``g.comm[e]`` (chain edges transfer nothing).
That structure is fixed per plan — the allocation decides once and for all
which edges pay — so noise only perturbs the *node* weights and a whole
batch of realizations evaluates as one ``vmap``ped ``lax.scan``.

Two granularities:

  * ``batch_makespans`` — one plan × (S,) noise realizations: the original
    single-graph path, one jit per augmented-DAG shape.
  * ``BatchedPlanDag`` + ``bucketed_makespans`` — *many different plans*
    (different DAGs, different n, different pred fan-in P) evaluated
    together: plans are grouped into buckets by the power-of-two envelope of
    (n, P), padded to the per-bucket maxima, and each bucket runs as ONE
    jitted vmap-over-plans of vmap-over-seeds scan.  A whole heterogeneous
    campaign — the (scenario × scheduler × seed) grid of
    ``benchmarks.campaign.sim_sweep`` — costs at most one XLA compile per
    bucket (``trace_count()`` exposes the actual number for tests).  When
    more than one device is visible the bucket's plan axis is sharded with
    ``shard_map`` over the explicit 1-D ``campaign_mesh()``; the plan axis
    is padded to a mesh-divisible count first (no divides-evenly
    assumption) and sliced back.  ``REPRO_SHARD_BACKEND`` selects the
    legacy ``pmap`` path or disables sharding for exact-parity checks.

Contended networks (``maxmin_fair``) are priced at plan-DAG *build* time:
by default a whole bucket of plans solves its replay/fluid fixpoint inside
one jitted program (``contended_bucket_delays`` below, built on
``network.fluid_finishes_jax``); ``set_contention_kernel("numpy")`` routes
through the per-plan numpy oracle instead.  Either way contention enters
``pred_delay`` as numbers, never as new array shapes.

Padding scheme: a plan with n tasks and max fan-in P lands in bucket
``(next_pow2(n), next_pow2(P))`` and is padded to that bucket's maxima —
phantom tasks have no predecessors and zero processing time, phantom order
slots point at a phantom task, so they finish at time 0 and never move the
max.  Padded entries of the times matrix are zero-filled by
``_pad_times``.

Release times and busy-machine conditioning enter as per-task start
*floors* (``PlanDag.floor``): a task starts no earlier than its floor, so a
rollout can replay a plan as if the machine's processors only became free
at their current commitment horizons (``rollout_floors``) — what the
``repro.streams`` simulation-in-the-loop policy evaluates candidates with.

``batch_makespans`` agrees with ``engine.simulate`` on shared seeds up to
float32 resolution (the repo runs JAX in its default 32-bit mode) — the
property tests assert rtol <= 1e-5.
"""
from __future__ import annotations

import dataclasses
import os
import threading
from collections import OrderedDict, defaultdict
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dag import TaskGraph
from repro.obs import registry as _obs

from .engine import Machine, NoiseModel, Plan

#: Compile-count kinds tracked by the jitted evaluators.  The increments
#: live *inside* the jitted function bodies, so each advances once per XLA
#: trace (shape bucket), not once per call.  Tests assert <= 1 per bucket.
#: The counts are the ``repro.obs`` counters ``sim.compile.<kind>``.
TRACE_KINDS = ("bucket", "single", "contended")


def _compile_counter(kind: str) -> str:
    if kind not in TRACE_KINDS:
        raise ValueError(f"unknown trace kind {kind!r}; "
                         f"valid kinds: {', '.join(TRACE_KINDS)}")
    return f"sim.compile.{kind}"


def trace_count(kind: str = "bucket") -> int:
    """XLA traces of the ``kind`` evaluator since process start (or the
    last :func:`reset_trace_counts`).  Raises ``ValueError`` on unknown
    kinds, listing the valid ones."""
    return _obs.counter_value(_compile_counter(kind))


def reset_trace_counts() -> None:
    """Zero every compile counter — test setup, so assertions read absolute
    counts instead of hand-rolled before/after deltas."""
    for kind in TRACE_KINDS:
        _obs.set_counter(_compile_counter(kind), 0)


# ---------------------------------------------------------------- plan DAGs
@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PlanDag:
    """Augmented (precedence + chain) DAG in padded device arrays."""

    order: jnp.ndarray       # (n,)   topological order of the augmented DAG
    pred: jnp.ndarray        # (n, P) padded predecessor ids, -1 = none
    pred_mask: jnp.ndarray   # (n, P) bool
    pred_delay: jnp.ndarray  # (n, P) transfer delay charged on that pred edge
    floor: jnp.ndarray       # (n,)   per-task earliest-start floor (release
                             #        time / busy-machine conditioning); 0 =
                             #        the classic closed-campaign replay
    width: jnp.ndarray       # (n,)   units each task occupies (moldable
                             #        decisions).  The replay scan does not
                             #        read it — a width-w task's occupancy is
                             #        already encoded as its w chain preds and
                             #        its curve-shrunk entry in ``times`` — but
                             #        the plan tensor carries the full
                             #        (type, width) decision so downstream
                             #        introspection (and the width-aware
                             #        samplers) never re-derive it.


def _plan_delay_override(g: TaskGraph, plan: Plan, network):
    """Per-edge delay vector a ``NetworkModel`` implies for this plan, or
    ``None`` for the default fixed-latency charging."""
    return _delay_overrides([(g, plan)], [network])[0]


def _delay_overrides(items, networks) -> list:
    """Per-item per-edge delay vectors (or ``None``) the models imply.

    Non-contended models reduce to closed-form delay arrays.  Contended
    models (``maxmin_fair``) price each plan through the fixed-start
    max-min fluid fixpoint; by default all contended items of the list are
    solved *together* by the jitted whole-bucket kernel
    (:func:`contended_bucket_delays` — one compile per padded-shape
    envelope), while ``set_contention_kernel("numpy")`` routes each through
    the per-plan numpy oracle ``contended_plan_delays`` instead.  Either
    way contention enters the plan DAG as delay *numbers*, never as new
    array shapes.
    """
    if networks is None:
        return [None] * len(items)
    out: list = [None] * len(items)
    contended = []
    for i, ((g, plan), net) in enumerate(zip(items, networks)):
        if net is None:
            continue
        if getattr(net, "contended", False):
            contended.append(i)
        else:
            out[i] = net.plan_delays(g, plan.alloc)
    if contended:
        from .network import contention_kernel
        if contention_kernel() == "numpy":
            from .engine import plan_times
            from .network import contended_plan_delays
            for i in contended:
                g, plan = items[i]
                out[i] = contended_plan_delays(
                    g, plan, plan_times(g, plan, g.proc), networks[i])
        else:
            delays = contended_bucket_delays([items[i] for i in contended],
                                             [networks[i] for i in contended])
            for i, d in zip(contended, delays):
                out[i] = d
    return out


def _plan_arrays(g: TaskGraph, plan: Plan, delay_e: np.ndarray | None = None):
    """Numpy (order, pred, delay, pred_eid) of the augmented DAG, minimally
    padded.  ``pred_eid[j, k]`` is the graph edge behind pred slot ``(j, k)``
    (−1 on chain/padding slots) — what maps pred slots to transfers when the
    contended kernel re-prices delays inside the compiled program."""
    n = g.n
    if delay_e is None:
        delay_e = g.edge_delays(plan.alloc)
    preds: list[list[int]] = [[] for _ in range(n)]
    delays: list[list[float]] = [[] for _ in range(n)]
    eids: list[list[int]] = [[] for _ in range(n)]
    for j in range(n):
        p0, p1 = g.pred_ptr[j], g.pred_ptr[j + 1]
        for i, eid in zip(g.pred_idx[p0:p1], g.pred_eid[p0:p1]):
            preds[j].append(int(i))
            delays[j].append(float(delay_e[eid]))
            eids[j].append(int(eid))
    for seq in plan.sequences.values():
        for a, b in zip(seq[:-1], seq[1:]):
            preds[b].append(a)
            delays[b].append(0.0)
            eids[b].append(-1)

    # Kahn over the augmented graph (it is acyclic by plan feasibility).
    succs: list[list[int]] = [[] for _ in range(n)]
    indeg = np.zeros(n, dtype=np.int64)
    for j, pj in enumerate(preds):
        indeg[j] = len(pj)
        for i in pj:
            succs[i].append(j)
    order = np.empty(n, dtype=np.int32)
    stack = list(np.flatnonzero(indeg == 0))
    head = 0
    while stack:
        u = int(stack.pop())
        order[head] = u
        head += 1
        for v in succs[u]:
            indeg[v] -= 1
            if indeg[v] == 0:
                stack.append(v)
    if head != n:
        raise ValueError("augmented plan graph has a cycle (infeasible plan)")

    P = max(1, max((len(p) for p in preds), default=1))
    pred = np.full((n, P), -1, dtype=np.int32)
    delay = np.zeros((n, P), dtype=np.float64)
    pred_eid = np.full((n, P), -1, dtype=np.int64)
    for j, pj in enumerate(preds):
        pred[j, : len(pj)] = pj
        delay[j, : len(pj)] = delays[j]
        pred_eid[j, : len(pj)] = eids[j]
    return order, pred, delay, pred_eid


def _plan_width(g: TaskGraph, plan: Plan) -> np.ndarray:
    """(n,) width column of a plan's decisions (ones on rigid plans)."""
    if plan.width is None:
        return np.ones(g.n, dtype=np.int32)
    return np.asarray(plan.width, dtype=np.int32)


def build_plan_dag(g: TaskGraph, plan: Plan,
                   floor: np.ndarray | None = None,
                   network=None) -> PlanDag:
    """Fuse DAG predecessors (with their transfer delays under the plan's
    allocation) with each task's processor-sequence predecessors (one chain
    pred per unit a width-w task occupies).

    ``floor`` optionally gives each task an earliest-start time (release
    times, or per-processor busy horizons when a rollout conditions on a
    non-idle machine — see ``rollout_floors``).  ``network`` optionally
    replaces the fixed-latency edge delays with a ``NetworkModel``'s
    (contended models solve the max-min fluid fixpoint — see
    ``_delay_overrides``)."""
    order, pred, delay, _ = _plan_arrays(
        g, plan, delay_e=_plan_delay_override(g, plan, network))
    f = np.zeros(g.n) if floor is None else np.asarray(floor, dtype=np.float64)
    return PlanDag(order=jnp.asarray(order), pred=jnp.asarray(pred),
                   pred_mask=jnp.asarray(pred >= 0),
                   pred_delay=jnp.asarray(delay), floor=jnp.asarray(f),
                   width=jnp.asarray(_plan_width(g, plan)))


def _one_makespan(dag: PlanDag, times: jnp.ndarray) -> jnp.ndarray:
    def step(finish, j):
        pf = jnp.where(dag.pred_mask[j],
                       finish[dag.pred[j]] + dag.pred_delay[j], 0.0)
        start = jnp.maximum(jnp.max(pf, initial=0.0), dag.floor[j])
        finish = finish.at[j].set(start + times[j])
        return finish, ()

    # zeros_like, not zeros: under shard_map the carry must inherit the
    # varying mesh axes of ``times`` or the scan's carry types disagree
    finish, _ = jax.lax.scan(step, jnp.zeros_like(times), dag.order)
    return jnp.max(finish)


def rollout_floors(g: TaskGraph, plan: Plan, busy: list[np.ndarray],
                   now: float = 0.0) -> np.ndarray:
    """(n,) start floors that condition a plan replay on a busy machine.

    ``busy[q]`` holds the commitment horizon of each type-q processor
    (``MachineState.busy_until(q)``); the first task of each per-processor
    sequence inherits the horizon of the processor its plan slot maps to
    (plan pids are matched to machine processors in ascending-horizon order,
    the same greedy order the engine commits in).  Times are relative to
    ``now`` so candidate rollouts at an arrival compare net makespans.
    """
    floor = np.zeros(g.n)
    for (q, pid), seq in plan.sequences.items():
        if seq:
            horizon = busy[q][pid] if pid < len(busy[q]) else 0.0
            floor[seq[0]] = max(0.0, float(horizon) - now)
    return floor


@jax.jit
def _batch_makespans(dag: PlanDag, times: jnp.ndarray) -> jnp.ndarray:
    _obs.bump("sim.compile.single")  # trace-time side effect: counts compiles
    return jax.vmap(partial(_one_makespan, dag))(times)


def batch_makespans(g: TaskGraph, plan: Plan, times: np.ndarray) -> np.ndarray:
    """Makespan of the plan replayed under each row of ``times`` (S, n)."""
    times = jnp.asarray(np.asarray(times, dtype=np.float64))
    if times.ndim != 2 or times.shape[1] != g.n:
        raise ValueError(f"times must be (S, n={g.n}), got {times.shape}")
    return np.asarray(_batch_makespans(build_plan_dag(g, plan), times))


#: Memo of noise multiplier grids, keyed by content: (kind, scale, n, the
#: seeds as int64 bytes) -> read-only (S, n) float64.  The draw is a pure
#: function of that key, so every plan of one size under one seed set
#: (common random numbers) shares one draw.  Bounded in entries and bytes.
_NOISE_CACHE_ENTRIES = 4
_NOISE_CACHE_BYTES = 64 << 20
_noise_cache: OrderedDict[tuple, np.ndarray] = OrderedDict()
_noise_cache_lock = threading.Lock()


def clear_noise_cache() -> None:
    """Drop every memoised noise grid (test isolation)."""
    with _noise_cache_lock:
        _noise_cache.clear()


def _noise_multipliers(noise: NoiseModel, seeds: np.ndarray,
                       n: int) -> np.ndarray:
    """(S, n) multipliers, row s drawn by ``default_rng(seeds[s])`` exactly
    as ``noise.sample`` draws it.  The array is the cache's own: read-only,
    never handed to callers.  Counters: ``noise_draws.hits`` / ``.misses``."""
    key = (noise.kind, noise.scale, n, seeds.tobytes())
    with _noise_cache_lock:
        mult = _noise_cache.get(key)
        if mult is not None:
            _noise_cache.move_to_end(key)
            _obs.bump("noise_draws.hits")
            return mult
        _obs.bump("noise_draws.misses")
    mult = np.empty((len(seeds), n))
    for row, s in zip(mult, seeds):
        row[:] = noise.multipliers(n, np.random.default_rng(int(s)))
    mult.setflags(write=False)
    if mult.nbytes <= _NOISE_CACHE_BYTES:
        with _noise_cache_lock:
            _noise_cache[key] = mult
            while (len(_noise_cache) > _NOISE_CACHE_ENTRIES
                   or sum(m.nbytes for m in _noise_cache.values())
                   > _NOISE_CACHE_BYTES):
                _noise_cache.popitem(last=False)
    return mult


def sample_actual_batch(g: TaskGraph, plan: Plan, noise: NoiseModel,
                        seeds) -> np.ndarray:
    """(S, n) realized times on each task's allocated type, one row per seed.

    Row s uses ``np.random.default_rng(seeds[s])`` exactly like
    ``engine.simulate(..., seed=seeds[s])`` — the two paths see identical
    noise streams.  The draw depends only on (noise, seeds, n), so it runs
    once per such key and is shared across plans (``_noise_multipliers``);
    each plan then gathers its allocated column in one vectorised step.
    Moldable decisions shrink each entry by the task's speedup curve at the
    plan's width (``engine.plan_times`` semantics, same operation order).
    """
    seeds = np.asarray(seeds, dtype=np.int64)
    idx = np.arange(g.n)
    base = g.proc[idx, np.asarray(plan.alloc, dtype=np.int64)]
    if noise.active:
        rows = base * _noise_multipliers(noise, seeds, g.n)
    else:
        rows = np.tile(base, (len(seeds), 1))
    if plan.width is not None and g.speedup is not None:
        rows = rows / g.speedup[idx, np.asarray(plan.width,
                                                dtype=np.int64) - 1]
    return rows


def sweep_makespans(g: TaskGraph, machine: Machine, scheduler, *,
                    noise: NoiseModel, seeds) -> np.ndarray:
    """Allocate once, evaluate the whole noise sweep in one vmapped pass."""
    plan = scheduler.allocate(g, machine)
    if plan is None:
        raise ValueError(f"{scheduler.name} is arrival-driven; "
                         "the batch path needs a static plan")
    return batch_makespans(g, plan, sample_actual_batch(g, plan, noise, seeds))


# ------------------------------------------------------- bucketed batch path
@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class BatchedPlanDag:
    """A bucket of B padded plan-DAGs stacked into one device-array pytree."""

    order: jnp.ndarray       # (B, n_pad) int32
    pred: jnp.ndarray        # (B, n_pad, P_pad) int32, -1 = none
    pred_mask: jnp.ndarray   # (B, n_pad, P_pad) bool
    pred_delay: jnp.ndarray  # (B, n_pad, P_pad) float
    floor: jnp.ndarray       # (B, n_pad) float — per-task start floors
    width: jnp.ndarray       # (B, n_pad) int32 — decision widths (phantom
                             #            tasks pad at width 1; see PlanDag)

    @property
    def batch(self) -> int:
        return self.order.shape[0]

    @property
    def n_pad(self) -> int:
        return self.order.shape[1]

    @staticmethod
    def from_plans(items: list[tuple[TaskGraph, Plan]],
                   floors: list[np.ndarray] | None = None,
                   pad_to: tuple[int, int] | None = None,
                   networks: list | None = None) -> "BatchedPlanDag":
        """Stack heterogeneous (graph, plan) pairs, padded to shared maxima.

        Items shorter than the bucket get phantom tasks: zero fan-in, zero
        time (``_pad_times``), and the item's spare order slots all point at
        the first phantom, so they finish at 0 and never move the max.  The
        bucket's largest item has no spare slots at all — unless ``pad_to``
        raises the padded shape to a fixed (n_pad, P_pad) envelope, which
        repeated small rollout calls use to hit one stable compiled shape.

        ``floors`` optionally carries per-item (n_i,) start floors (release
        times / busy-machine conditioning); phantom tasks floor at 0.
        ``networks`` optionally carries a per-item ``NetworkModel`` (or
        ``None``) replacing the fixed-latency edge delays — contention
        enters as numbers in ``pred_delay``, never as new array shapes.
        """
        delay_es = _delay_overrides(items, networks)
        arrays = [_plan_arrays(g, plan, delay_e=delay_es[i])
                  for i, (g, plan) in enumerate(items)]
        n_pad = max(a[0].shape[0] for a in arrays)
        P_pad = max(a[1].shape[1] for a in arrays)
        if pad_to is not None:
            n_pad, P_pad = max(n_pad, pad_to[0]), max(P_pad, pad_to[1])
        B = len(arrays)
        order = np.zeros((B, n_pad), dtype=np.int32)
        pred = np.full((B, n_pad, P_pad), -1, dtype=np.int32)
        delay = np.zeros((B, n_pad, P_pad), dtype=np.float64)
        floor = np.zeros((B, n_pad), dtype=np.float64)
        width = np.ones((B, n_pad), dtype=np.int32)
        for b, (o, p, d, _) in enumerate(arrays):
            n, Pi = p.shape
            order[b, :n] = o
            order[b, n:] = n  # empty slice for the bucket's largest item
            pred[b, :n, :Pi] = p
            delay[b, :n, :Pi] = d
            width[b, :n] = _plan_width(items[b][0], items[b][1])
            if floors is not None:
                floor[b, :n] = floors[b]
        return BatchedPlanDag(order=jnp.asarray(order),
                              pred=jnp.asarray(pred),
                              pred_mask=jnp.asarray(pred >= 0),
                              pred_delay=jnp.asarray(delay),
                              floor=jnp.asarray(floor),
                              width=jnp.asarray(width))


def _pad_times(times: np.ndarray, n_pad: int) -> np.ndarray:
    """(S, n) -> (S, n_pad), phantom tasks take zero time."""
    S, n = times.shape
    if n == n_pad:
        return times
    out = np.zeros((S, n_pad), dtype=times.dtype)
    out[:, :n] = times
    return out


def _bucket_key(g: TaskGraph, plan: Plan) -> tuple[int, int]:
    """Power-of-two envelope of (n + 1 phantom slot, max augmented fan-in).

    The augmented fan-in is bounded by the DAG fan-in plus one chain pred
    per unit of the widest decision (1 on rigid plans); using the bound
    (instead of the exact value) keeps the key cheap and stable.
    """
    n = g.n
    fan = int(np.diff(g.pred_ptr).max()) if g.n else 0
    p = fan + (int(plan.width.max()) if plan.width is not None else 1)
    return (1 << int(np.ceil(np.log2(max(n + 1, 2)))),
            1 << int(np.ceil(np.log2(max(p, 1)))))


def bucket_plans(items: list[tuple[TaskGraph, Plan]]
                 ) -> dict[tuple[int, int], list[int]]:
    """Group item indices by padded-shape bucket."""
    buckets: dict[tuple[int, int], list[int]] = defaultdict(list)
    for i, (g, plan) in enumerate(items):
        buckets[_bucket_key(g, plan)].append(i)
    return dict(buckets)


@jax.jit
def _bucket_makespans(bd: BatchedPlanDag, times: jnp.ndarray) -> jnp.ndarray:
    _obs.bump("sim.compile.bucket")  # trace-time side effect: counts compiles

    def per_item(order, pred, mask, delay, floor, width, t):
        return jax.vmap(partial(_one_makespan,
                                PlanDag(order, pred, mask, delay, floor,
                                        width)))(t)

    with jax.named_scope("replay_scan"):
        return jax.vmap(per_item)(bd.order, bd.pred, bd.pred_mask,
                                  bd.pred_delay, bd.floor, bd.width, times)


# -------------------------------------------------- contended bucket kernel
@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ContendedBucket:
    """A bucket of B padded plans plus their transfer sets, stacked for the
    jitted whole-bucket contention fixpoint (``_contended_durations``)."""

    order: jnp.ndarray      # (B, n_pad) int32 topological order
    pred: jnp.ndarray       # (B, n_pad, P_pad) int32, -1 = none
    pred_mask: jnp.ndarray  # (B, n_pad, P_pad) bool
    pred_tid: jnp.ndarray   # (B, n_pad, P_pad) int32 transfer behind each
                            #      pred slot, -1 = chain/non-cross/padding
    times: jnp.ndarray      # (B, n_pad) float nominal (noise-free) durations
    src: jnp.ndarray        # (B, T_pad) int32 producer task per transfer
    size: jnp.ndarray       # (B, T_pad) float data-object sizes
    up: jnp.ndarray         # (B, T_pad) int32 dense uplink ids
    dn: jnp.ndarray         # (B, T_pad) int32 dense downlink ids
    t_mask: jnp.ndarray     # (B, T_pad) bool real-transfer lanes
    capacity: jnp.ndarray   # (B,) float link bandwidth per plan


@partial(jax.jit, static_argnums=(1, 2))
def _contended_durations(cb: ContendedBucket, num_links: int,
                         iters: int) -> jnp.ndarray:
    """(B, T_pad) fluid transfer durations at the replay/fluid fixpoint.

    The traceable mirror of :func:`repro.sim.network.contended_plan_delays`
    for a whole bucket at once: each round replays every plan's augmented
    DAG under the current durations (the same ``lax.scan`` recurrence the
    makespan path runs — transfer starts are the producers' finishes), then
    re-solves the fixed-start max-min fluid sub-problem with the masked
    event kernel :func:`repro.sim.network.fluid_finishes_jax`.  Plans whose
    durations stop moving (the oracle's ``allclose(rtol=1e-3, atol=1e-9)``
    break criterion, applied per lane) freeze, so the fixed ``iters``-round
    ``fori_loop`` reproduces the oracle's early-exit schedule exactly.  One
    XLA trace per padded shape (``trace_count("contended")``).
    """
    from .network import fluid_finishes_jax

    _obs.bump("sim.compile.contended")  # trace-time side effect: compiles

    def per_plan(order, pred, mask, tid, times, src, size, up, dn,
                 t_mask, cap):
        fdt = times.dtype
        zero = jnp.zeros((), fdt)
        dur0 = jnp.where(t_mask, size / cap, zero)

        def replay(dur):
            pd = jnp.where(tid >= 0, dur[jnp.maximum(tid, 0)], zero)

            def step(finish, j):
                pf = jnp.where(mask[j], finish[pred[j]] + pd[j], zero)
                start = jnp.max(pf, initial=0.0)
                return finish.at[j].set(start + times[j]), ()

            finish, _ = jax.lax.scan(step, jnp.zeros(times.shape[0], fdt),
                                     order)
            return finish

        def round_fn(_, carry):
            dur, done = carry
            starts = replay(dur)[src]
            fin = fluid_finishes_jax(starts, size, up, dn, t_mask, cap,
                                     num_links)
            new = jnp.where(t_mask, fin - starts, zero)
            close = jnp.all((jnp.abs(new - dur)
                             <= 1e-9 + 1e-3 * jnp.abs(dur)) | ~t_mask)
            return jnp.where(done, dur, new), done | close

        with jax.named_scope("contention_fixpoint"):
            dur, _ = jax.lax.fori_loop(0, iters, round_fn,
                                       (dur0, jnp.array(False)))
        return dur

    return jax.vmap(per_plan)(cb.order, cb.pred, cb.pred_mask, cb.pred_tid,
                              cb.times, cb.src, cb.size, cb.up, cb.dn,
                              cb.t_mask, cb.capacity)


def _pow2(x: int) -> int:
    return 1 << max(0, int(np.ceil(np.log2(max(int(x), 1)))))


def contended_bucket_delays(items: list, networks: list) -> list[np.ndarray]:
    """Per-item (E_i,) per-edge delay vectors from the jitted whole-bucket
    contention fixpoint — the batched front door ``_delay_overrides`` calls.

    Items are grouped by ``(bucket_key, num_links)`` — the same
    power-of-two (n, fan-in) envelope the makespan path buckets by — and
    each group's transfer axis is padded to the power-of-two envelope of
    its largest transfer set, so a campaign's contended grid costs at most
    one ``_contended_durations`` compile per bucket; plans with no
    crossing transfers short-circuit to zeros.  The kernel runs under
    ``jax.enable_x64(True)`` so the fixpoint matches the float64
    numpy oracle to rtol 1e-6; the resulting durations scatter back to the
    (deduplicated, output-cached) edges via ``PlanTransfers.key_of``.
    """
    from .engine import plan_times
    from .network import CONTENTION_ITERS, plan_transfers

    out: list[np.ndarray | None] = [None] * len(items)
    groups: dict[tuple, list[int]] = defaultdict(list)
    prep: list[tuple | None] = [None] * len(items)
    for i, ((g, plan), net) in enumerate(zip(items, networks)):
        tr = plan_transfers(g, plan, net)
        if not tr.count:
            out[i] = np.zeros(g.num_edges)
            continue
        arrays = _plan_arrays(g, plan, delay_e=np.zeros(g.num_edges))
        prep[i] = (tr, arrays, plan_times(g, plan, g.proc))
        n_pad, P_pad = _bucket_key(g, plan)
        groups[(n_pad, P_pad, tr.num_links)].append(i)

    for (n_pad, P_pad, L), idxs in groups.items():
        B = len(idxs)
        T_pad = _pow2(max(prep[i][0].count for i in idxs))
        order = np.zeros((B, n_pad), dtype=np.int32)
        pred = np.full((B, n_pad, P_pad), -1, dtype=np.int32)
        tid = np.full((B, n_pad, P_pad), -1, dtype=np.int32)
        times = np.zeros((B, n_pad), dtype=np.float64)
        src = np.zeros((B, T_pad), dtype=np.int32)
        size = np.zeros((B, T_pad), dtype=np.float64)
        up = np.zeros((B, T_pad), dtype=np.int32)
        dn = np.zeros((B, T_pad), dtype=np.int32)
        t_mask = np.zeros((B, T_pad), dtype=bool)
        cap = np.zeros(B, dtype=np.float64)
        for b, i in enumerate(idxs):
            tr, (o, p, _, pe), base = prep[i]
            n, Pi = p.shape
            order[b, :n] = o
            order[b, n:] = n  # spare slots visit the first phantom task
            pred[b, :n, :Pi] = p
            m = pe >= 0
            ti = np.full((n, Pi), -1, dtype=np.int32)
            ti[m] = tr.key_of[pe[m]]
            tid[b, :n, :Pi] = ti
            times[b, :n] = base
            T = tr.count
            src[b, :T] = tr.src
            size[b, :T] = tr.size
            up[b, :T] = tr.up
            dn[b, :T] = tr.dn
            t_mask[b, :T] = True
            cap[b] = tr.capacity
        with _obs.span("sim.contended.fixpoint", bucket=f"{n_pad}x{P_pad}",
                       links=L, plans=B), jax.enable_x64(True):
            cb = ContendedBucket(
                order=jnp.asarray(order), pred=jnp.asarray(pred),
                pred_mask=jnp.asarray(pred >= 0), pred_tid=jnp.asarray(tid),
                times=jnp.asarray(times), src=jnp.asarray(src),
                size=jnp.asarray(size), up=jnp.asarray(up),
                dn=jnp.asarray(dn), t_mask=jnp.asarray(t_mask),
                capacity=jnp.asarray(cap))
            durs = np.asarray(_contended_durations(cb, L, CONTENTION_ITERS))
        for b, i in enumerate(idxs):
            tr = prep[i][0]
            g = items[i][0]
            delay = np.zeros(g.num_edges)
            hit = tr.key_of >= 0
            delay[hit] = durs[b, tr.key_of[hit]]
            out[i] = delay
    return out  # type: ignore[return-value]


# ------------------------------------------------------- mesh execution layer
_PLAN_AXIS = "plans"
_SHARD_BACKENDS = ("shard_map", "pmap", "none")
_MESH = None
_SHARD_FNS: dict = {}


def campaign_mesh():
    """The explicit 1-D device mesh (axis ``"plans"``) the bucketed
    evaluator shards each bucket's plan axis over — lazily built across all
    of ``jax.devices()``.  On a single-device host the mesh is trivial and
    every bucket takes the single-program path, so CPU CI is unchanged."""
    global _MESH
    if _MESH is None:
        from jax.sharding import Mesh
        _MESH = Mesh(np.asarray(jax.devices()), (_PLAN_AXIS,))
    return _MESH


def set_campaign_mesh(mesh) -> None:
    """Install a custom campaign mesh (``None`` resets to the all-device
    default).  The mesh must be 1-D with axis name ``"plans"``."""
    global _MESH
    if mesh is not None and tuple(mesh.axis_names) != (_PLAN_AXIS,):
        raise ValueError(f"campaign mesh must have the single axis "
                         f"{_PLAN_AXIS!r}, got {mesh.axis_names}")
    _MESH = mesh


def shard_backend() -> str:
    """Which execution backend shards the plan axis: ``shard_map`` (the
    mesh path, default), ``pmap`` (the legacy per-device path), or ``none``
    (always single-program).  Env ``REPRO_SHARD_BACKEND`` selects."""
    backend = os.environ.get("REPRO_SHARD_BACKEND", "shard_map")
    if backend not in _SHARD_BACKENDS:
        raise ValueError(f"unknown REPRO_SHARD_BACKEND={backend!r}; "
                         f"have {_SHARD_BACKENDS}")
    return backend


def _pad_plan_axis(bd: BatchedPlanDag, times: jnp.ndarray, multiple: int):
    """Pad the plan axis to a multiple of the shard count by repeating item
    0 (a real plan, so padded lanes trace the same program), returning
    ``(bd, times, B)`` with the original plan count for the round-trip
    slice.  This is what lifts the divides-evenly assumption: any plan
    count — prime counts included — shards after padding."""
    B = times.shape[0]
    pad = (-B) % multiple
    if not pad:
        return bd, times, B
    take = np.r_[np.arange(B), np.zeros(pad, dtype=np.int64)]
    bd = jax.tree_util.tree_map(lambda a: a[take], bd)
    times = jnp.concatenate([times, jnp.repeat(times[:1], pad, 0)], axis=0)
    return bd, times, B


def _shard_fn(mesh):
    """One jitted shard_map wrapper per mesh (cached, so repeated buckets
    reuse the compiled program — ``trace_count('bucket')`` still counts one
    trace per bucket shape because the wrapped body is the counter)."""
    fn = _SHARD_FNS.get(mesh)
    if fn is None:
        from jax.sharding import PartitionSpec
        spec = PartitionSpec(_PLAN_AXIS)
        fn = jax.jit(jax.shard_map(_bucket_makespans.__wrapped__,
                                   mesh=mesh, in_specs=(spec, spec),
                                   out_specs=spec))
        _SHARD_FNS[mesh] = fn
    return fn


def _bucket_makespans_pmap(bd: BatchedPlanDag, times: jnp.ndarray,
                           D: int) -> jnp.ndarray:
    """Legacy pmap sharding, kept as a comparison backend.  The plan axis
    is padded to a device-divisible count first (historically this path
    silently required ``B % local_device_count() == 0`` whenever the padded
    gather was skipped) and the result is sliced back."""
    bd, times, B = _pad_plan_axis(bd, times, D)
    shard = jax.tree_util.tree_map(
        lambda a: a.reshape(D, -1, *a.shape[1:]), (bd, times))
    out = jax.pmap(_bucket_makespans.__wrapped__)(*shard)
    return out.reshape(-1, out.shape[-1])[:B]


def _bucket_makespans_sharded(bd: BatchedPlanDag, times: jnp.ndarray,
                              mesh=None) -> jnp.ndarray:
    """Shard the plan axis of one bucket across the campaign mesh.

    The default backend wraps the jitted vmapped scan in ``shard_map`` over
    the explicit 1-D ``campaign_mesh()`` (``jax.sharding`` path); the plan
    axis is padded to a mesh-divisible count (``_pad_plan_axis``) and
    sliced back, with the round-trip shape asserted.  Because the program
    is purely per-plan (a vmap), the sharded result equals the
    single-device result bit-for-bit.  Single-device meshes (CPU CI) and
    tiny buckets fall back to the single program unchanged.
    """
    backend = shard_backend()
    B, S = times.shape[0], times.shape[1]
    if backend == "pmap":
        D = jax.local_device_count()
        if D <= 1 or B < 2:
            return _bucket_makespans(bd, times)
        out = _bucket_makespans_pmap(bd, times, D)
    elif backend == "shard_map":
        mesh = campaign_mesh() if mesh is None else mesh
        D = int(mesh.devices.size)
        if D <= 1 or B < 2:
            return _bucket_makespans(bd, times)
        bdp, tp, _ = _pad_plan_axis(bd, times, D)
        with _obs.span("sim.shard.dispatch", backend="shard_map",
                       devices=D, plans=B):
            out = _shard_fn(mesh)(bdp, tp)[:B]
    else:   # "none": always the single program
        return _bucket_makespans(bd, times)
    assert out.shape == (B, S), \
        f"plan-axis round trip broke: {out.shape} != {(B, S)}"
    return out


def bucketed_makespans(items: list[tuple[TaskGraph, Plan]],
                       times: list[np.ndarray],
                       floors: list[np.ndarray] | None = None,
                       envelope: bool = False,
                       networks: list | None = None,
                       mesh=None) -> list[np.ndarray]:
    """Replay many different plans under per-plan times matrices.

    Args:
      items: (graph, plan) pairs — arbitrary mixed sizes.
      times: matching (S, n_i) realized-time matrices; S must agree across
             items (one campaign = one seed grid).
      floors: optional matching (n_i,) per-task start floors (release times
             or busy-machine conditioning, see ``rollout_floors``).
      envelope: pad every bucket to its full power-of-two (n, fan-in)
             envelope instead of the per-call maxima, so *repeated* calls
             with same-bucket items (the simulation-in-the-loop rollout
             pattern) reuse one compiled shape instead of retracing.
      networks: optional matching per-item ``NetworkModel`` (or ``None``)
             entries — edge delays are replaced at plan-DAG build time
             (contended models via the jitted whole-bucket fluid fixpoint),
             so the bucketed path stays at <= 1 XLA compile per bucket.
      mesh: optional explicit device mesh to shard each bucket's plan axis
             over (defaults to ``campaign_mesh()``; single-device meshes
             run the plain single program).

    Returns a list of (S,) makespan arrays, one per item, in input order.
    Cost: one jitted vmapped scan per *bucket* (power-of-two envelope of
    (n, fan-in)), not per item — ``trace_count('bucket')`` measures it.
    """
    if len(items) != len(times):
        raise ValueError("items and times must align")
    if floors is not None and len(floors) != len(items):
        raise ValueError("floors and items must align")
    if networks is not None and len(networks) != len(items):
        raise ValueError("networks and items must align")
    if not items:
        return []
    S = {t.shape[0] for t in times}
    if len(S) != 1:
        raise ValueError(f"all items must share one seed grid, got S={sorted(S)}")
    for (g, _), t in zip(items, times):
        if t.ndim != 2 or t.shape[1] != g.n:
            raise ValueError(f"times must be (S, n={g.n}), got {t.shape}")

    out: list[np.ndarray | None] = [None] * len(items)
    for key, idxs in bucket_plans(items).items():
        with _obs.span("sim.bucket.build", bucket=f"{key[0]}x{key[1]}",
                       plans=len(idxs)):
            bd = BatchedPlanDag.from_plans(
                [items[i] for i in idxs],
                floors=([floors[i] for i in idxs]
                        if floors is not None else None),
                pad_to=key if envelope else None,
                networks=([networks[i] for i in idxs]
                          if networks is not None else None))
            tt = np.stack([_pad_times(np.asarray(times[i], dtype=np.float64),
                                      bd.n_pad) for i in idxs])
        with _obs.span("sim.bucket.execute", bucket=f"{key[0]}x{key[1]}",
                       plans=len(idxs)):
            ms = np.asarray(_bucket_makespans_sharded(bd, jnp.asarray(tt),
                                                      mesh=mesh))
        for row, i in enumerate(idxs):
            out[i] = ms[row]
    return out  # type: ignore[return-value]


def fixed_envelope_makespans(items: list[tuple[TaskGraph, Plan]],
                             times: list[np.ndarray],
                             pad_to: tuple[int, int],
                             floors: list[np.ndarray] | None = None,
                             mesh=None) -> list[np.ndarray]:
    """Replay many plans as ONE bucket padded to a caller-fixed envelope.

    :func:`bucketed_makespans` keys each plan by its own power-of-two
    envelope, so a population whose widths straddle a power-of-two boundary
    splits into several buckets whose composition shifts call to call — and
    the per-call plan count B is part of the traced shape.  Iterative
    searches (``repro.search.evolve_plan``) instead pin BOTH axes: every
    call pads all plans to the same ``pad_to = (n_pad, P_pad)`` envelope
    and the caller keeps ``len(items)`` constant (padding with repeats), so
    an entire generation loop retraces nothing after its first batch.

    Every item must FIT the envelope — a plan larger than ``pad_to`` would
    silently grow the compiled shape, so it raises instead.

    Returns a list of (S,) makespan arrays, one per item, in input order.
    """
    if len(items) != len(times):
        raise ValueError("items and times must align")
    if not items:
        return []
    S = {t.shape[0] for t in times}
    if len(S) != 1:
        raise ValueError(f"all items must share one seed grid, got S={sorted(S)}")
    for (g, _), t in zip(items, times):
        if t.ndim != 2 or t.shape[1] != g.n:
            raise ValueError(f"times must be (S, n={g.n}), got {t.shape}")
    with _obs.span("sim.bucket.build", bucket=f"{pad_to[0]}x{pad_to[1]}",
                   plans=len(items)):
        bd = BatchedPlanDag.from_plans(items, floors=floors, pad_to=pad_to)
        if (bd.n_pad, bd.pred.shape[2]) != tuple(pad_to):
            raise ValueError(
                f"item exceeds the fixed envelope {tuple(pad_to)}: bucket "
                f"padded to {(bd.n_pad, bd.pred.shape[2])}")
        tt = np.stack([_pad_times(np.asarray(t, dtype=np.float64), bd.n_pad)
                       for t in times])
    with _obs.span("sim.bucket.execute", bucket=f"{pad_to[0]}x{pad_to[1]}",
                   plans=len(items)):
        ms = np.asarray(_bucket_makespans_sharded(bd, jnp.asarray(tt),
                                                  mesh=mesh))
    return [ms[i] for i in range(len(items))]


def search_envelope(g: TaskGraph, machine) -> tuple[int, int]:
    """The fixed power-of-two envelope covering EVERY legal plan of
    ``(g, machine)`` — what :func:`fixed_envelope_makespans` pads to so a
    whole search (any allocation, any legal widths) shares one compiled
    shape.  Matches :func:`_bucket_key` at the graph's maximum legal width,
    so rigid-graph searches land in the same bucket the campaign sweeps
    already compiled."""
    from repro.platform import as_platform

    counts = as_platform(machine, warn=False).to_counts()
    n = g.n
    fan = int(np.diff(g.pred_ptr).max()) if g.n else 0
    wcap = max(1, min(int(g.max_width), max(counts)))
    return (_pow2(n + 1), _pow2(fan + wcap))


def sweep_suite_makespans(entries, *, noise: NoiseModel, seeds,
                          floor_fn=None, envelope: bool = False,
                          network=None, mesh=None, workers: int = 1,
                          cache: bool = False) -> list[np.ndarray]:
    """One-jit-per-bucket campaign sweep over heterogeneous (g, machine,
    scheduler) entries: allocate each plan once, sample its noise grid with
    the engine-identical streams, and evaluate every (entry × seed) makespan
    through the bucketed batch path.

    ``floor_fn(g, plan) -> (n,)`` optionally conditions each replay on
    per-task start floors (busy machine / release times); ``envelope=True``
    pads to the full bucket envelope so repeated small sweeps — the
    simulation-in-the-loop rollout pattern of ``repro.streams.policy`` —
    stay at one XLA compile per shape bucket across calls.  ``network``
    applies one ``NetworkModel`` to every entry's replay; ``mesh``
    overrides the campaign mesh the plan axis shards over.

    ``workers`` and ``cache`` route through the *pipelined* executor
    (:func:`repro.sim.pipeline.pipelined_sweep_makespans`): plan
    construction fans out over ``workers`` pool workers (``None`` reads
    ``REPRO_PLAN_WORKERS``), ``cache=True`` deduplicates allocations
    through the content-addressed plan cache, and buckets dispatch as soon
    as they close so host building overlaps device execution.  The default
    ``workers=1, cache=False`` is this serial loop, unchanged; either
    route returns bit-identical makespans (envelope/phantom padding cannot
    move a real lane's result).

    Returns a list of (S,) arrays aligned with ``entries``.
    """
    if workers is None or workers != 1 or cache:
        from .pipeline import pipelined_sweep_makespans
        return pipelined_sweep_makespans(
            entries, noise=noise, seeds=seeds, floor_fn=floor_fn,
            network=network, workers=workers, cache=cache, mesh=mesh)
    items, rows, floors = [], [], []
    for g, machine, scheduler in entries:
        plan = scheduler.allocate(g, machine)
        if plan is None:
            raise ValueError(f"{scheduler.name} is arrival-driven; "
                             "the batch path needs a static plan")
        items.append((g, plan))
        rows.append(sample_actual_batch(g, plan, noise, seeds))
        if floor_fn is not None:
            floors.append(np.asarray(floor_fn(g, plan), dtype=np.float64))
    return bucketed_makespans(items, rows,
                              floors=floors if floor_fn is not None else None,
                              envelope=envelope,
                              networks=([network] * len(items)
                                        if network is not None else None),
                              mesh=mesh)
