"""Public-API snapshot — breaks surface in PRs, not in user code.

``__all__`` of the scheduling-facing packages is pinned; additions are
fine (extend the snapshot in the same PR, with the changelog naming them),
but a *removal or rename* fails here first.  Every exported name must also
resolve to a real attribute.
"""
import importlib

import pytest

API = {
    "repro.platform": [
        "Decision", "PLATFORMS", "Platform", "PoolState", "as_decision",
        "as_platform", "decisions_of", "default_type_names", "pack_decisions",
    ],
    "repro.core": [
        "AllocationProblem", "CPU", "GPU", "HLPSolution", "RULES", "Schedule",
        "TaskGraph",
        "amdahl_speedup", "brute_force_opt", "brute_force_schedule",
        "canonical_round_moldable", "decide_eft", "decide_erls",
        "efficient_width", "er_ls", "eft_online",
        "erls_decide", "erls_decide_moldable", "frac_objective",
        "greedy_online", "heft",
        "hlp_est", "hlp_ols", "list_schedule", "lp_lower_bound",
        "makespan_lower_bound", "mhlp_choices", "ols_rank", "powerlaw_speedup",
        "random_online", "solve_hlp", "solve_mhlp", "solve_qhlp",
        "validate_speedup",
    ],
    "repro.sim": [
        "ADAPTERS", "Decision", "FixedLatencyNetwork", "FrozenPlanScheduler",
        "InstantNetwork", "Machine", "MachineState", "MaxMinFairNetwork",
        "NETWORKS", "NetworkModel", "NoiseModel", "Plan", "Platform",
        "SCENARIO_FAMILIES", "Scenario", "Scheduler", "SimResult",
        "TraceEvent", "cached_allocate", "campaign_mesh",
        "clear_plan_cache", "configure_xla_cache",
        "default_suite", "from_estee", "last_pipeline_stats", "make_network",
        "make_scenario", "make_scheduler", "moldable_suite",
        "pipelined_sweep_makespans", "plan_cache_stats", "plan_for",
        "plan_times", "plan_workers", "reset_trace_counts",
        "set_campaign_mesh",
        "simulate", "to_estee", "trace_count",
    ],
    "repro.obs": [
        "CHROME_REQUIRED_KEYS", "DecisionRecord", "bump", "capture",
        "counter_value", "counters", "decision_records", "disable",
        "dump_decisions", "enable", "enabled", "explain_divergence",
        "export_chrome_trace", "gauges", "load_chrome_trace",
        "provenance_diff", "record_decision", "reset", "set_counter",
        "set_gauge", "sim_trace_events", "snapshot", "span",
        "stream_trace_events", "timer", "transfer_trace_events",
        "wall_events", "wall_trace_events",
    ],
    "repro.streams": [
        "AdapterPolicy", "COMM_CANDIDATES", "ClosedLoopSource",
        "DEFAULT_CANDIDATES", "DEFAULT_JOB_PARAMS", "Job",
        "JobFactory", "JobRecord", "MMPPProcess", "OpenLoopSource",
        "PoissonProcess", "SEARCH_CANDIDATES", "SimInTheLoop", "StreamPolicy",
        "StreamResult",
        "TaskRecord", "TenantLedger", "bounded_slowdown", "chameleon_stream",
        "job_slowdowns", "make_policy", "mean_queue_length", "open_stream",
        "queue_length_series", "replay_estee", "run_stream", "tenant_summary",
        "utilization",
    ],
    "repro.search": [
        "METHODS", "Genome", "SearchConfig", "SearchResult", "alloc_crossover",
        "brute_force_gap", "evolve_plan", "genome_to_plan", "is_topo_perm",
        "lp_seed_plan", "mutate_alloc", "mutate_perm", "order_crossover",
        "plan_to_genome", "random_genome", "seed_plans", "topo_perm",
        "width_caps",
    ],
}


@pytest.mark.parametrize("module", sorted(API))
def test_public_api_surface(module):
    mod = importlib.import_module(module)
    assert sorted(mod.__all__) == sorted(API[module]), (
        f"{module}.__all__ drifted — update tests/test_api_surface.py in "
        f"the same PR and call the change out in the changelog")
    for name in mod.__all__:
        assert getattr(mod, name, None) is not None, f"{module}.{name}"


def test_adapter_registry_covers_the_moldable_planner():
    from repro.sim import ADAPTERS
    assert "mhlp_ols" in ADAPTERS


def test_adapter_registry_covers_the_comm_aware_allocators():
    from repro.sim import ADAPTERS
    assert "cahlp_ols" in ADAPTERS and "camhlp_ols" in ADAPTERS


def test_adapter_registry_covers_the_plan_search():
    from repro.sim import ADAPTERS
    assert "evo" in ADAPTERS and "evo_camhlp" in ADAPTERS


def test_scenario_registry_covers_the_moldable_family():
    from repro.sim import SCENARIO_FAMILIES
    assert "moldable_cholesky" in SCENARIO_FAMILIES
