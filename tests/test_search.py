"""repro.search: anytime dominance, oracle match, compile budget, adapters.

The contract under test, in order of importance:
  * **anytime dominance** — generation 0 already scores the raw LP / HEFT /
    ER-LS plans, so the result can never be worse than the best of them;
  * **oracle match** — at n ≤ 10 a modest search budget reaches the
    branch-and-bound optimum;
  * **compile budget** — a whole multi-generation run costs exactly one
    XLA compile (fixed envelope + fixed batch width);
  * the ``evo``/``evo_camhlp`` adapters and the ``search`` bench registry
    entry exist and plug into the standard pipelines.
"""
import jax
import numpy as np
import pytest

from repro.search import (Genome, SearchConfig, evolve_plan, genome_to_plan,
                          plan_to_genome, seed_plans)
from repro.sim import make_scheduler, plan_for, simulate
from repro.sim.batch import reset_trace_counts, search_envelope, trace_count
from repro.sim.scenarios import (default_suite, layered_scenario,
                                 random_scenario)


def _heuristic_makespans(sc):
    """Clean makespans of the seed heuristics, via the scalar engine —
    independently of the search's own fitness path."""
    out = {}
    for name in ("hlp_ols", "heft", "er_ls"):
        out[name] = simulate(sc.graph, sc.machine,
                             make_scheduler(name)).makespan
    return out


@pytest.mark.parametrize("sc", default_suite(seed=0), ids=lambda s: s.name)
def test_gen0_best_dominates_the_heuristic_seeds(sc):
    res = evolve_plan(sc.graph, sc.machine,
                      SearchConfig(pop_size=8, generations=0), seed=0)
    best_heur = min(_heuristic_makespans(sc).values())
    # fitness is the float32 bucketed replay of the same plans the scalar
    # engine times in float64 — allow that representation slack only
    assert res.gen0_best <= best_heur * (1 + 1e-5)
    assert res.fitness == res.gen0_best == min(res.history)


def test_final_best_never_worse_than_seeds_across_methods():
    sc = layered_scenario(n=40, layers=5, seed=3, ccr=1.0)
    for method in ("ga", "cem", "sa"):
        res = evolve_plan(sc.graph, sc.machine,
                          SearchConfig(method=method, pop_size=12,
                                       generations=4, comm_aware=True),
                          seed=2)
        assert res.fitness <= min(res.seed_fitness.values()) + 1e-9
        assert res.fitness == min(res.history)
        assert len(res.history) == 5


@pytest.mark.parametrize("seed", [7, 11, 13])
def test_bruteforce_exact_match_at_small_n(seed):
    from repro.core.bruteforce import brute_force_schedule
    sc = random_scenario(n=8, seed=seed, counts=(3, 2))
    opt = brute_force_schedule(sc.graph, sc.machine).makespan
    res = evolve_plan(sc.graph, sc.machine,
                      SearchConfig(pop_size=32, generations=10), seed=0)
    assert res.fitness == pytest.approx(opt, rel=1e-5)


def test_whole_search_is_one_xla_compile():
    sc = layered_scenario(n=35, layers=5, seed=5)
    jax.clear_caches()   # a shape compiled by an earlier test is no retrace
    reset_trace_counts()
    for method in ("ga", "cem", "sa"):
        evolve_plan(sc.graph, sc.machine,
                    SearchConfig(method=method, pop_size=16, generations=6),
                    seed=0)
    # same graph + same pop size -> same fixed (envelope, batch) shape:
    # three full searches, eighteen generations, one compile
    assert trace_count("bucket") == 1


def test_evolve_plan_is_bit_reproducible():
    sc = random_scenario(n=30, seed=9)
    cfg = SearchConfig(pop_size=16, generations=6)
    a = evolve_plan(sc.graph, sc.machine, cfg, seed=42)
    b = evolve_plan(sc.graph, sc.machine, cfg, seed=42)
    assert a.fitness == b.fitness and a.history == b.history
    assert np.array_equal(a.genome.types, b.genome.types)
    assert np.array_equal(a.genome.widths, b.genome.widths)
    assert np.array_equal(a.genome.perm, b.genome.perm)
    assert np.array_equal(a.plan.alloc, b.plan.alloc)
    assert a.evals == b.evals and a.cache_hits == b.cache_hits


def test_genome_plan_roundtrip_preserves_fitness():
    sc = layered_scenario(n=25, layers=5, seed=1)
    plans = seed_plans(sc.graph, sc.machine)
    for name, plan in plans.items():
        gn = plan_to_genome(sc.graph, sc.machine, plan)
        assert isinstance(gn, Genome)
        rebuilt = genome_to_plan(sc.graph, sc.machine, gn)
        # the genome's list-schedule replay of the plan's own priorities
        # may legally re-pack, but never to a *worse* makespan than a
        # from-scratch heuristic would explain; sanity: same allocation
        assert np.array_equal(rebuilt.alloc, plan.alloc)


def test_evo_adapters_ride_the_standard_pipeline():
    sc = layered_scenario(n=20, layers=4, seed=0, ccr=0.5)
    for name in ("evo", "evo_camhlp"):
        res = simulate(sc.graph, sc.machine, make_scheduler(name))
        assert res.schedule.makespan > 0
        assert plan_for(name, sc.graph, sc.machine) is not None
    heur = min(_heuristic_makespans(sc).values())
    evo_ms = simulate(sc.graph, sc.machine, make_scheduler("evo")).makespan
    assert evo_ms <= heur * (1 + 1e-5)


def test_search_envelope_is_fixed_and_fits_every_genome():
    sc = random_scenario(n=22, seed=4)
    pad_to = search_envelope(sc.graph, sc.machine)
    rng = np.random.default_rng(0)
    from repro.search import random_genome
    from repro.sim.batch import fixed_envelope_makespans
    from repro.sim.engine import plan_times
    g = sc.graph
    plans = [genome_to_plan(g, sc.machine, random_genome(g, sc.machine, rng))
             for _ in range(5)]
    rows = [plan_times(g, p, g.proc)[None, :] for p in plans]
    out = fixed_envelope_makespans([(g, p) for p in plans], rows, pad_to)
    assert len(out) == 5 and all(float(o[0]) > 0 for o in out)


def test_search_counters_and_gauge_land_in_obs():
    from repro import obs
    sc = layered_scenario(n=20, layers=4, seed=2)
    obs.enable()
    try:
        obs.reset()
        before = dict(obs.counters())   # counters are cumulative by design
        res = evolve_plan(sc.graph, sc.machine,
                          SearchConfig(pop_size=8, generations=3), seed=0)
        ctrs = obs.counters()
        assert (ctrs.get("search.evals", 0)
                - before.get("search.evals", 0)) == res.evals
        assert (ctrs.get("search.cache_hits", 0)
                - before.get("search.cache_hits", 0)) == res.cache_hits
        assert obs.gauges().get("search.best_fitness") == pytest.approx(
            res.fitness)
        spans = [e for e in obs.wall_events()
                 if e.get("name") == "search.generation"]
        assert len(spans) == 4    # gen 0 + 3
        recs = [r for r in obs.decision_records()
                if r.scheduler == "evo:ga"]   # the er_ls seed rollout
                                              # records its own decisions
        assert len(recs) == sc.graph.n
        assert all(r.tie_break.startswith("perm:") for r in recs)
    finally:
        obs.disable()
        obs.reset()


def test_search_config_rejects_unknown_method_and_tiny_pop():
    with pytest.raises(ValueError, match="unknown search method"):
        SearchConfig(method="hillclimb")
    with pytest.raises(ValueError, match="pop_size"):
        SearchConfig(pop_size=1)
