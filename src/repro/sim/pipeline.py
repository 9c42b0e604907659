"""repro.sim.pipeline — the pipelined campaign executor.

The campaign harness pays for the paper's allocate/schedule separation
serially: every LP solve, HEFT insertion and ER-LS replay runs one-by-one
on the host before a single bucketed makespan batch is dispatched to the
device mesh (``sweep_suite_makespans``).  This module overlaps the three
phases instead:

  1. **Parallel plan construction** — ``scheduler.allocate(g, machine)``
     fans out over a worker pool (``REPRO_PLAN_WORKERS``, default
     ``os.cpu_count()``): a *process* pool for the HiGHS/LP-heavy adapters
     (``plan_pool = "process"``), threads for the pure-numpy ones.  Results
     are gathered in submission order, so schedules stay bit-identical to
     the serial path — ``workers=1`` *is* the serial path.

  2. **A content-addressed plan cache** — :func:`cached_allocate` keys a
     finished ``Plan`` by (TaskGraph fingerprint, scheduler name + config,
     platform, network knob), so the static/moldable/netbound sub-grids and
     the simulation-in-the-loop rollouts stop re-solving identical
     allocations across seeds and network models.  Hits and misses land in
     the always-on obs counters ``plan_cache.hits`` / ``plan_cache.misses``;
     the cache returns the *same* ``Plan`` object the solver produced, so
     recording on/off cannot perturb a schedule (zero observer effect).

  3. **Host/device overlap** — every entry's shape bucket (its
     ``search_envelope``) is known *before* its plan is, so bucket
     membership is fixed up front and each bucket is dispatched to the
     sharded evaluator the moment its last plan lands.  JAX async dispatch
     returns immediately; plan-building and noise-sampling for bucket k+1
     then overlap device execution of bucket k, and the host blocks only in
     a final drain.  ``sim.pipeline.*`` spans time the stages and
     :func:`last_pipeline_stats` reports the measured ``overlap_frac``.

  4. **Persistent XLA compilation cache** — :func:`configure_xla_cache`
     keeps compiled programs where ``JAX_COMPILATION_CACHE_DIR`` says, or
     at one fixed path in the checkout, so warm campaign runs skip
     recompilation entirely.

Because buckets pad to the *envelope* (every legal plan of (g, machine)
fits), the whole pipeline still costs <= 1 XLA compile per bucket
(``trace_count("bucket")``-asserted in tests), and phantom/padding lanes
cannot move a real makespan — the pipelined sweep equals
``sweep_suite_makespans`` bit-for-bit.
"""
from __future__ import annotations

import dataclasses
import hashlib
import itertools
import multiprocessing
import os
import threading
import time
from concurrent.futures import (Executor, Future, ProcessPoolExecutor,
                                ThreadPoolExecutor)
from concurrent.futures.process import BrokenProcessPool
from contextlib import nullcontext

import numpy as np

import jax.numpy as jnp

from repro.core.dag import TaskGraph
from repro.obs import registry as _obs
from repro.sim.batch import (BatchedPlanDag, _bucket_makespans_sharded,
                             _pad_times, sample_actual_batch, search_envelope)
from repro.sim.engine import NoiseModel, Plan

__all__ = [
    "cached_allocate", "cached_solve", "clear_plan_cache",
    "configure_xla_cache", "graph_fingerprint", "last_pipeline_stats",
    "pipelined_sweep_makespans", "plan_cache_stats", "plan_workers",
]


# ------------------------------------------------------------------- knobs
def plan_workers() -> int:
    """Worker count for parallel plan construction: ``REPRO_PLAN_WORKERS``
    when set, else ``os.cpu_count()``.  ``1`` means build serially on the
    calling thread (bit-identical by construction, trivially)."""
    raw = os.environ.get("REPRO_PLAN_WORKERS", "").strip()
    if raw:
        return max(1, int(raw))
    return max(1, os.cpu_count() or 1)


#: The persistent compilation cache's one fixed home when
#: ``JAX_COMPILATION_CACHE_DIR`` is unset: ``.jax_cache`` at the root of the
#: checkout (git-ignored).  The directory is part of the cache key, so it
#: never moves between runs.
XLA_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def configure_xla_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    that directory stands; otherwise the cache goes to :data:`XLA_CACHE_DIR`.
    Minimum compile time / entry size are zeroed either way: campaign
    buckets are many small programs, and a warm run should skip *all* of
    them.  Entry points (``benchmarks.run``, ``chip_smoke.py``) call this
    before their first compile; importing the package never does."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if not path:
        path = XLA_CACHE_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


# ------------------------------------------------------------ fingerprints
def _hash_update(h, value) -> None:
    if isinstance(value, np.ndarray):
        h.update(str((value.dtype.str, value.shape)).encode())
        h.update(np.ascontiguousarray(value).tobytes())
    else:
        h.update(repr(value).encode())
    h.update(b"|")


def graph_fingerprint(g: TaskGraph) -> str:
    """SHA-256 over every field of the (frozen) ``TaskGraph`` — array bytes
    with dtype/shape tags, scalars by repr.  Content-addressed: two graphs
    with equal arrays share a fingerprint regardless of identity.  Cached on
    the instance (graphs are immutable)."""
    fp = getattr(g, "_repro_fingerprint", None)
    if fp is not None:
        return fp
    h = hashlib.sha256()
    for f in dataclasses.fields(g):
        h.update(f.name.encode() + b"=")
        _hash_update(h, getattr(g, f.name))
    fp = h.hexdigest()
    object.__setattr__(g, "_repro_fingerprint", fp)
    return fp


def plan_fingerprint(plan: Plan) -> str:
    """SHA-256 of a plan's schedule content (alloc / proc / widths / per-proc
    sequences) — the golden-hash identity tests pin."""
    h = hashlib.sha256()
    _hash_update(h, np.asarray(plan.alloc))
    _hash_update(h, np.asarray(plan.proc))
    if plan.width is not None:
        _hash_update(h, np.asarray(plan.width))
    _hash_update(h, sorted((tuple(int(x) for x in k),
                            tuple(int(t) for t in v))
                           for k, v in plan.sequences.items()))
    return h.hexdigest()


def _platform_fingerprint(machine) -> str:
    from repro.platform import as_platform

    return repr(as_platform(machine, warn=False))


_SIMPLE = (bool, int, float, str, bytes, type(None), tuple, frozenset)


def _scheduler_fingerprint(scheduler) -> str | None:
    """Stable (name + config) identity of a scheduler instance, or ``None``
    when the adapter opts out of caching (``cacheable = False``, e.g.
    ``FrozenPlanScheduler``) or carries config the fingerprint cannot see.

    Config is every simple public instance attribute plus dataclass configs
    by repr; adapters holding anything else (open files, arrays, callables
    beyond the name-carrying rule table) are refused rather than mis-keyed.
    """
    if not getattr(scheduler, "cacheable", True):
        return None
    parts = [type(scheduler).__name__, getattr(scheduler, "name", "?")]
    for k, v in sorted(vars(scheduler).items()):
        if k.startswith("_"):
            continue
        if isinstance(v, _SIMPLE):
            parts.append(f"{k}={v!r}")
        elif dataclasses.is_dataclass(v) and not isinstance(v, type):
            parts.append(f"{k}={v!r}")
        elif callable(v):
            # name-carrying strategy hooks (greedy rule fns): the adapter
            # ``name`` already encodes which one — key on that
            parts.append(f"{k}=fn:{getattr(v, '__name__', '?')}")
        else:
            return None
    return "|".join(parts)


def plan_cache_key(g: TaskGraph, machine, scheduler,
                   network=None) -> tuple | None:
    """The content address of one allocation, or ``None`` when this
    scheduler cannot be cached.  ``network`` keys allocators that consume a
    network model at allocate time (today's adapters don't — contention
    awareness is scheduler *config* and already fingerprinted)."""
    sfp = _scheduler_fingerprint(scheduler)
    if sfp is None:
        return None
    net_key = None if network is None else getattr(
        network, "name", type(network).__name__)
    return (graph_fingerprint(g), sfp, _platform_fingerprint(machine), net_key)


# -------------------------------------------------------------- plan cache
_PLAN_CACHE: dict[tuple, Plan | None] = {}
_PLAN_CACHE_LOCK = threading.Lock()


def clear_plan_cache() -> None:
    """Drop every cached allocation (the hit/miss counters keep counting)."""
    with _PLAN_CACHE_LOCK:
        _PLAN_CACHE.clear()


def plan_cache_stats() -> dict[str, int]:
    """Cumulative ``plan_cache.hits`` / ``plan_cache.misses`` counter values
    plus the current entry count."""
    return {"hits": _obs.counter_value("plan_cache.hits"),
            "misses": _obs.counter_value("plan_cache.misses"),
            "entries": len(_PLAN_CACHE)}


def cached_allocate(scheduler, g: TaskGraph, machine, *,
                    network=None, cache: bool = True):
    """``scheduler.allocate(g, machine)`` through the content-addressed plan
    cache.  A hit returns the very ``Plan`` object the original solve
    produced (plans are immutable by convention), so results are bit-
    identical with the cache on or off; arrival-driven adapters
    (``allocate() -> None``) and uncacheable schedulers pass straight
    through.  Counters: ``plan_cache.hits`` / ``plan_cache.misses``."""
    key = plan_cache_key(g, machine, scheduler, network=network) if cache \
        else None
    if key is not None:
        with _PLAN_CACHE_LOCK:
            if key in _PLAN_CACHE:
                _obs.bump("plan_cache.hits")
                return _PLAN_CACHE[key]
    plan = scheduler.allocate(g, machine)
    if key is not None:
        _obs.bump("plan_cache.misses")
        if plan is not None:
            with _PLAN_CACHE_LOCK:
                _PLAN_CACHE[key] = plan
    return plan


def cached_solve(kind: str, g: TaskGraph, machine, solve, *, extra=()):
    """The plan cache for named deterministic plan builders that aren't
    adapter instances — e.g. the search's generation-0 seed plans
    (``lp_seed_plan``, one ``plan_for`` rollout per heuristic), which are
    re-solved identically for every search seed.  ``kind`` names the
    builder, ``extra`` carries its config knobs; ``solve()`` runs on a
    miss.  Same counters and same object-identity hit semantics as
    :func:`cached_allocate`."""
    key = ("solve", kind, graph_fingerprint(g),
           _platform_fingerprint(machine), tuple(extra))
    with _PLAN_CACHE_LOCK:
        if key in _PLAN_CACHE:
            _obs.bump("plan_cache.hits")
            return _PLAN_CACHE[key]
    plan = solve()
    _obs.bump("plan_cache.misses")
    if plan is not None:
        with _PLAN_CACHE_LOCK:
            _PLAN_CACHE[key] = plan
    return plan


# ------------------------------------------------- parallel plan construction
def _allocate_timed(scheduler, g, machine, tags=None):
    """Worker-side allocate, returning ``(plan, solve_seconds, recorded)``.
    Top-level so the process pool can pickle it by reference.

    ``tags`` is ``None`` while the parent's registry is disabled; otherwise
    the task runs under :class:`repro.obs.registry.collect` with those tags,
    and ``recorded`` carries its spans and decision records back for the
    parent to merge.  A process worker has a registry of its own, a thread
    worker shares the parent's: either way the buffer keeps them apart.
    Span times need no mapping: ``perf_counter`` is ``CLOCK_MONOTONIC``,
    shared by every process of the host."""
    scope = _obs.collect(**tags) if tags is not None else nullcontext()
    with scope as recorded:
        t0 = time.perf_counter()
        plan = scheduler.allocate(g, machine)
        dt = time.perf_counter() - t0
    return plan, dt, recorded


def _pool_kind(scheduler) -> str:
    """Which pool an adapter's allocate belongs on: ``"process"`` for the
    HiGHS/LP-heavy solvers (sidestep the GIL), ``"thread"`` for pure-numpy
    or JAX-backed ones (must stay in-process).  ``REPRO_PLAN_POOL`` forces
    ``thread``/``process``/``serial`` for every adapter."""
    forced = os.environ.get("REPRO_PLAN_POOL", "").strip().lower()
    if forced in ("thread", "process", "serial"):
        return forced
    return getattr(scheduler, "plan_pool", "thread")


# The LP-heavy pool is process-based and *persistent*: started once at
# first use and reused by every later build, so the worker-startup cost is
# paid once per campaign, not once per sweep.  The ``forkserver`` context
# matters twice over: workers must never fork the parent directly (forking
# a process with live JAX/XLA threads can deadlock) and must not re-import
# ``__main__`` (``spawn`` breaks under REPLs and unguarded scripts) — the
# forkserver is a cleanly exec'd interpreter that forks *itself*.  Workers
# are pinned to JAX's CPU backend (``_host_only_worker``): an accelerator
# belongs to one process, and the parent that dispatches the buckets holds it.
_PROCESS_POOL: ProcessPoolExecutor | None = None
_PROCESS_POOL_SIZE = 0
# flipped after a BrokenProcessPool (e.g. an unguarded/REPL __main__ that
# the start method cannot re-import): LP-heavy work then routes to the
# thread pool for the rest of the session instead of re-breaking per sweep
_PROCESS_POOL_DISABLED = False


def _host_only_worker() -> None:
    """Process-pool initializer: whatever a worker's ``allocate`` touches
    in JAX initializes the CPU backend, never the parent's accelerator.
    (Unpickling this function imported jax already, so the environment is
    read no more: the config is set directly.)"""
    import jax

    jax.config.update("jax_platforms", "cpu")


def _process_pool(workers: int) -> ProcessPoolExecutor:
    global _PROCESS_POOL, _PROCESS_POOL_SIZE
    if _PROCESS_POOL is None or _PROCESS_POOL_SIZE < workers:
        if _PROCESS_POOL is not None:
            _PROCESS_POOL.shutdown(wait=False)
        ctx = "forkserver" if "forkserver" in \
            multiprocessing.get_all_start_methods() else "spawn"
        _PROCESS_POOL = ProcessPoolExecutor(
            max_workers=workers,
            mp_context=multiprocessing.get_context(ctx),
            initializer=_host_only_worker)
        _PROCESS_POOL_SIZE = workers
    return _PROCESS_POOL


def _reset_process_pool(disable: bool = False) -> None:
    global _PROCESS_POOL, _PROCESS_POOL_SIZE, _PROCESS_POOL_DISABLED
    if _PROCESS_POOL is not None:
        _PROCESS_POOL.shutdown(wait=False)
    _PROCESS_POOL, _PROCESS_POOL_SIZE = None, 0
    if disable:
        _PROCESS_POOL_DISABLED = True


def build_plans(entries, *, workers: int | None = None, cache: bool = True,
                network=None) -> tuple[list, float]:
    """Allocate a plan for every ``(g, machine, scheduler)`` entry, fanning
    the solves over the worker pools, deduplicating through the plan cache
    (identical in-flight entries solve once), and returning
    ``(plans_in_entry_order, total_solve_seconds)``.

    Deterministic by construction: futures are gathered in submission
    order, every solver is deterministic, and cache hits return the
    original ``Plan`` object — so the result list is bit-identical for any
    ``workers`` and cache setting.  While obs recording is enabled each
    worker returns its spans and decision records with its plan
    (:func:`_allocate_timed`), tagged like the calling thread's spans, and
    they are merged in submission order: the decision records are the
    same, in the same order, for any ``workers``.
    """
    workers = plan_workers() if workers is None else max(1, int(workers))
    tags = _obs.tags() if _obs.enabled() else None
    results: list = [None] * len(entries)
    build_s = 0.0

    # in-flight dedup: first entry per cache key solves, the rest alias it
    owner: dict[tuple, int] = {}
    alias: dict[int, int] = {}
    keys: list[tuple | None] = []
    for i, (g, machine, sched) in enumerate(entries):
        key = plan_cache_key(g, machine, sched, network=network) if cache \
            else None
        keys.append(key)
        if key is not None and key in owner:
            alias[i] = owner[key]
        elif key is not None:
            owner[key] = i

    if workers == 1:
        for i, (g, machine, sched) in enumerate(entries):
            if i in alias:
                _obs.bump("plan_cache.hits")
                results[i] = results[alias[i]]
                continue
            t0 = time.perf_counter()
            results[i] = cached_allocate(sched, g, machine, network=network,
                                         cache=cache)
            build_s += time.perf_counter() - t0
        return results, build_s

    thread_pool: list[Executor] = []

    def pool_for(kind: str) -> Executor:
        if kind == "process" and not _PROCESS_POOL_DISABLED:
            return _process_pool(workers)
        if not thread_pool:
            thread_pool.append(ThreadPoolExecutor(max_workers=workers))
        return thread_pool[0]

    try:
        futures: dict[int, object] = {}
        for i, (g, machine, sched) in enumerate(entries):
            if i in alias:
                continue
            key = keys[i]
            if key is not None:
                with _PLAN_CACHE_LOCK:
                    if key in _PLAN_CACHE:
                        _obs.bump("plan_cache.hits")
                        results[i] = _PLAN_CACHE[key]
                        continue
            kind = _pool_kind(sched)
            if kind == "serial":
                # inline, but gathered in order with the pooled results
                futures[i] = Future()
                futures[i].set_result(_allocate_timed(sched, g, machine,
                                                      tags))
            else:
                futures[i] = pool_for(kind).submit(
                    _allocate_timed, sched, g, machine, tags)
        for i, fut in futures.items():
            try:
                plan, dt, recorded = fut.result()
            except BrokenProcessPool:
                # a spawn-hostile __main__ (stdin/REPL) or a killed worker:
                # solvers are deterministic, so recomputing inline keeps
                # bit-identity — the pool is dropped, not retried
                _reset_process_pool(disable=True)
                _obs.bump("plan_pool.broken")
                g_i, machine_i, sched_i = entries[i]
                plan, dt, recorded = _allocate_timed(sched_i, g_i, machine_i,
                                                     tags)
            if recorded is not None:
                _obs.merge(recorded)
            build_s += dt
            results[i] = plan
            key = keys[i]
            if key is not None:
                _obs.bump("plan_cache.misses")
                if plan is not None:
                    with _PLAN_CACHE_LOCK:
                        _PLAN_CACHE[key] = plan
        for i, j in alias.items():
            _obs.bump("plan_cache.hits")
            results[i] = results[j]
    finally:
        for p in thread_pool:
            p.shutdown(wait=True)
    return results, build_s


# ------------------------------------------------------ pipelined executor
@dataclasses.dataclass
class PipelineStats:
    """What one :func:`pipelined_sweep_makespans` run measured."""

    plans: int = 0
    buckets: int = 0
    workers: int = 1
    plan_build_s: float = 0.0    # summed solver seconds (all workers)
    dispatch_s: float = 0.0      # the dispatch loop: the three below
    sample_s: float = 0.0        # noise grids of every plan
    assemble_s: float = 0.0      # plan-DAG assembly, padding, stacking
    launch_s: float = 0.0        # device transfer + async bucket dispatch
    drain_s: float = 0.0         # blocking device sync at the end
    total_s: float = 0.0
    overlap_s: float = 0.0       # host work done with >= 1 bucket in flight
    overlap_frac: float = 0.0    # overlap_s / total_s
    cache_hits: int = 0
    cache_misses: int = 0


_LAST_STATS = PipelineStats()
_CALLS = itertools.count(1)   # the ``call`` tag of each sweep's spans


def last_pipeline_stats() -> PipelineStats:
    """Stats of the most recent :func:`pipelined_sweep_makespans` call."""
    return _LAST_STATS


def pipelined_sweep_makespans(entries, *, noise: NoiseModel = None, seeds=(),
                              sample_fn=None, floor_fn=None,
                              network=None, networks=None,
                              workers: int | None = None, cache: bool = True,
                              mesh=None) -> list[np.ndarray]:
    """The pipelined drop-in for :func:`repro.sim.batch.sweep_suite_makespans`:
    same ``(g, machine, scheduler)`` entries, same ``(S,)``-array-per-entry
    result, bit-identical values — built by the parallel/cached/overlapped
    executor instead of the serial loop.

    ``sample_fn(g, plan) -> (S, n)`` overrides the default noise grid
    (``sample_actual_batch(g, plan, noise, seeds)``); ``networks`` is an
    optional per-entry ``NetworkModel`` list (``network`` applies one model
    to every entry).  ``workers=1`` builds plans serially;
    ``workers=None`` reads ``REPRO_PLAN_WORKERS``.

    Buckets are keyed by :func:`search_envelope` — known from ``(g,
    machine)`` *before* the plan exists — so each bucket dispatches to the
    sharded evaluator the moment its last member's plan lands, and JAX
    async dispatch overlaps device execution with the remaining host-side
    building.  Padding to the envelope cannot move a real makespan (phantom
    lanes finish at 0), so values match the serial path exactly while the
    per-(g, machine) compiled shape is shared with ``repro.search``'s
    fixed-envelope evaluator.
    """
    global _LAST_STATS
    t_start = time.perf_counter()
    stats = PipelineStats(plans=len(entries),
                          workers=plan_workers() if workers is None
                          else max(1, int(workers)))
    hits0 = _obs.counter_value("plan_cache.hits")
    misses0 = _obs.counter_value("plan_cache.misses")
    if not entries:
        _LAST_STATS = stats
        return []
    if networks is not None and len(networks) != len(entries):
        raise ValueError("networks and entries must align")
    if networks is None and network is not None:
        networks = [network] * len(entries)

    # bucket membership is fixed before any plan exists: the envelope key
    # depends only on (g, machine), so a bucket "closes" (and dispatches)
    # the moment its last member's plan is built
    keys = [search_envelope(g, machine) for g, machine, _ in entries]
    members: dict[tuple[int, int], list[int]] = {}
    for i, key in enumerate(keys):
        members.setdefault(key, []).append(i)
    stats.buckets = len(members)

    with _obs.tagged(call=next(_CALLS)):
        with _obs.span("sim.pipeline.build", plans=len(entries),
                       buckets=len(members), workers=stats.workers):
            plans, stats.plan_build_s = build_plans(
                entries, workers=workers, cache=cache, network=None)
        for (g, machine, scheduler), plan in zip(entries, plans):
            if plan is None:
                raise ValueError(f"{scheduler.name} is arrival-driven; "
                                 "the batch path needs a static plan")
        # the dispatch loop: one timer per part, each both the span and the
        # ``PipelineStats`` counter it adds to, so the two cannot disagree
        pending = {key: len(idxs) for key, idxs in members.items()}
        grids: dict[int, np.ndarray] = {}
        in_flight: list[tuple[tuple[int, int], list[int], object]] = []
        first_dispatch = None
        with _obs.timer("sim.pipeline.dispatch", plans=len(entries),
                        buckets=len(members)) as disp:
            for i, ((g, machine, _), plan) in enumerate(zip(entries, plans)):
                with _obs.timer("sim.pipeline.sample", plan=i) as t:
                    grids[i] = np.asarray(
                        sample_fn(g, plan) if sample_fn is not None
                        else sample_actual_batch(g, plan, noise, seeds),
                        dtype=np.float64)
                stats.sample_s += t.dur
                key = keys[i]
                pending[key] -= 1
                if pending[key]:
                    continue
                idxs = members[key]
                bucket = f"{key[0]}x{key[1]}"
                with _obs.timer("sim.pipeline.assemble", bucket=bucket,
                                plans=len(idxs)) as t:
                    items = [(entries[j][0], plans[j]) for j in idxs]
                    bd = BatchedPlanDag.from_plans(
                        items, pad_to=key,
                        floors=([np.asarray(floor_fn(g_j, p_j),
                                            dtype=np.float64)
                                 for g_j, p_j in items]
                                if floor_fn is not None else None),
                        networks=([networks[j] for j in idxs]
                                  if networks is not None else None))
                    if (bd.n_pad, bd.pred.shape[2]) != key:
                        raise AssertionError(
                            f"plan escaped its envelope {key}: bucket "
                            f"padded to {(bd.n_pad, bd.pred.shape[2])}")
                    tt = np.stack([_pad_times(grids.pop(j), bd.n_pad)
                                   for j in idxs])
                stats.assemble_s += t.dur
                with _obs.timer("sim.pipeline.launch", bucket=bucket) as t:
                    # async dispatch: the device starts here, the host
                    # moves on
                    ms = _bucket_makespans_sharded(bd, jnp.asarray(tt),
                                                   mesh=mesh)
                stats.launch_s += t.dur
                in_flight.append((key, idxs, ms))
                if first_dispatch is None:
                    first_dispatch = time.perf_counter()
        stats.dispatch_s = disp.dur
        t_drain0 = disp.t0 + disp.dur
        out: list[np.ndarray | None] = [None] * len(entries)
        with _obs.span("sim.pipeline.drain", buckets=len(in_flight)):
            for key, idxs, ms in in_flight:
                ms = np.asarray(ms)   # blocks until this bucket's work ends
                for row, j in enumerate(idxs):
                    out[j] = ms[row]
    t_end = time.perf_counter()
    stats.drain_s = t_end - t_drain0
    stats.total_s = t_end - t_start
    stats.overlap_s = max(0.0, t_drain0 - first_dispatch) \
        if first_dispatch is not None else 0.0
    stats.overlap_frac = stats.overlap_s / stats.total_s if stats.total_s \
        else 0.0
    stats.cache_hits = _obs.counter_value("plan_cache.hits") - hits0
    stats.cache_misses = _obs.counter_value("plan_cache.misses") - misses0
    _LAST_STATS = stats
    return out  # type: ignore[return-value]
