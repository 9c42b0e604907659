"""repro.sim — discrete-event scheduler simulation over one unified protocol.

The paper's experimental section (§6) is a large simulation campaign: run
every algorithm (HLP-EST/OLS, HEFT, ER-LS, greedy rules, …) over libraries
of task graphs and machine configurations, and compare makespans against the
LP lower bound.  This package unifies them behind one ``Scheduler`` protocol
and one event-driven engine (design after ESTEE, Kobzol et al.), built on
the v2 allocation API of ``repro.platform``:

  * **machines are ``Platform`` objects** — typed pools with canonical
    names and counts.  ``Machine`` is the simulation-facing subclass;
    legacy bare ``counts`` lists still work through a deprecation shim.
  * **decisions are ``Decision`` records** — an allocation is
    ``(type, width)``, not a bare int.  On *moldable* graphs
    (``TaskGraph.speedup`` curves) a width-w task claims w units of one
    pool and shrinks by its curve: schedulers search widths (MHLP's
    width-indexed LP, width-aware HEFT/ER-LS/EFT), the engine commits them
    atomically, and ``width=1`` reproduces the paper's rigid model
    bit-for-bit (golden-tested).

Beyond the paper's static pipeline it adds:

  * **stochastic runtimes** — ``proc`` entries are *estimates*; the engine
    perturbs them with a seeded ``NoiseModel`` (lognormal / uniform) and
    replays static plans dynamically, so robustness-to-misprediction becomes
    measurable;
  * **communication costs** — edges may carry transfer costs
    (``TaskGraph.comm``), charged by every scheduler and by the engine
    whenever a dependence crosses the CPU/GPU type boundary; scenario
    families expose this as a CCR knob and ``ccr=0`` reproduces the
    communication-free behavior bit-for-bit;
  * **pluggable network models** — ``repro.sim.network`` makes *how*
    transfers cost time swappable: ``instant`` (free), ``fixed_latency``
    (today's per-edge delays, bit-for-bit), and ``maxmin_fair`` (fluid
    contention: concurrent cross-type transfers of sized data objects
    share per-type links under max-min fairness, reused outputs ship
    once).  ``simulate(..., network=...)`` charges it in the engine,
    ``run_stream(..., network=...)`` in the open system, and the bucketed
    JAX path takes a vectorized sharing approximation;
  * **arrival streams** — tasks may carry release times, turning any offline
    instance into an online one;
  * **scenario families** — ``repro.sim.scenarios`` generates the paper's
    workloads (chains, fork-join, layered/STG, tiled Cholesky/LU), the
    network-bound ``netbound`` instance, the moldable ``moldable_cholesky``
    family (per-kernel Amdahl curves), and a bridge to
    ``repro.core.workloads``;
  * **a pipelined campaign executor** — ``repro.sim.pipeline`` overlaps the
    three campaign phases: plan construction fans out over a worker pool
    (``REPRO_PLAN_WORKERS``; process pool for LP-heavy adapters, threads
    for numpy/JAX ones), a content-addressed plan cache
    (``cached_allocate``) deduplicates identical allocations across
    sub-grids / seeds / network models, and each shape bucket dispatches to
    the device the moment it closes so host building overlaps device
    execution (``pipelined_sweep_makespans``, bit-identical to the serial
    sweep; ``last_pipeline_stats`` reports the measured overlap).
    ``configure_xla_cache`` turns on JAX's persistent compilation cache
    (``JAX_COMPILATION_CACHE_DIR``, else one fixed path in the checkout)
    so warm runs skip recompiling entirely;
  * **a padded/bucketed JAX path** — ``repro.sim.batch`` evaluates a whole
    heterogeneous campaign of static plans: plans are grouped by the
    power-of-two envelope of (tasks, fan-in), padded to per-bucket maxima,
    and each bucket runs as one jitted vmapped scan (≤ 1 XLA compile per
    bucket), its plan axis sharded with ``shard_map`` over the explicit
    1-D ``campaign_mesh()`` when several devices are visible
    (``set_campaign_mesh`` installs a custom mesh, ``REPRO_SHARD_BACKEND``
    selects the legacy ``pmap`` path or disables sharding).  Contended
    networks are priced by a jitted whole-bucket fluid fixpoint
    (``contention_kernel``/``set_contention_kernel`` switch to the numpy
    oracle).  Plan tensors carry the full (type, width) decision — the
    width column rides along and realized times are curve-shrunk before
    the scan.

Entry points::

    from repro.platform import Platform
    from repro.sim import simulate, make_scheduler, ADAPTERS
    from repro.sim.scenarios import default_suite

    for sc in default_suite(seed=0):
        for name in ADAPTERS:
            r = simulate(sc.graph, sc.machine, make_scheduler(name),
                         noise=NoiseModel("lognormal", 0.1), seed=sc.seed)
            print(sc.name, name, r.makespan)
"""
from repro.platform import Decision, Platform

from .adapters import ADAPTERS, FrozenPlanScheduler, make_scheduler, plan_for
from .batch import (campaign_mesh, reset_trace_counts, set_campaign_mesh,
                    shard_backend, trace_count)
from .engine import (Machine, MachineState, NoiseModel, Plan, Scheduler,
                     SimResult, TraceEvent, plan_times, simulate)
from .network import (NETWORKS, FixedLatencyNetwork, InstantNetwork,
                      MaxMinFairNetwork, NetworkModel, contention_kernel,
                      make_network, set_contention_kernel)
from .pipeline import (cached_allocate, clear_plan_cache, configure_xla_cache,
                       last_pipeline_stats, pipelined_sweep_makespans,
                       plan_cache_stats, plan_workers)
from .scenarios import (SCENARIO_FAMILIES, Scenario, default_suite,
                        from_estee, make_scenario, moldable_suite, to_estee)

__all__ = [
    "ADAPTERS", "FrozenPlanScheduler", "make_scheduler", "plan_for",
    "Decision", "Platform", "Machine", "MachineState", "NoiseModel", "Plan",
    "Scheduler", "SimResult", "TraceEvent", "plan_times", "simulate",
    "NETWORKS", "NetworkModel", "InstantNetwork", "FixedLatencyNetwork",
    "MaxMinFairNetwork", "contention_kernel", "make_network",
    "set_contention_kernel",
    "campaign_mesh", "set_campaign_mesh", "shard_backend",
    "reset_trace_counts", "trace_count",
    "cached_allocate", "clear_plan_cache", "configure_xla_cache",
    "last_pipeline_stats", "pipelined_sweep_makespans", "plan_cache_stats",
    "plan_workers",
    "SCENARIO_FAMILIES", "Scenario", "default_suite", "from_estee",
    "make_scenario", "moldable_suite", "to_estee",
]
