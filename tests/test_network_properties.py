"""Property-based tests for the network layer (needs the dev extra).

Invariants:

  * ``maxmin_rates`` — per-link rate sums never exceed capacity, and every
    flow gets at least its fair share ``min_l capacity / n_l`` (the defining
    max-min property);
  * ``fixed_latency`` == the default engine on random comm-carrying DAGs —
    identical makespans and start vectors for every static adapter;
  * network models are ordered: instant ≤ fixed_latency ≤ maxmin_fair on
    any plan (contention only ever adds delay).
"""
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # dev extra: pip install -r requirements-dev.txt
from hypothesis import given, settings, strategies as st

from repro.sim import Machine, make_network, make_scheduler, simulate
from repro.sim.network import maxmin_rates
from conftest import random_dag

LINKS = [("up", 0), ("down", 0), ("up", 1), ("down", 1), ("up", 2), ("down", 2)]


@st.composite
def flow_sets(draw):
    F = draw(st.integers(1, 12))
    flows = []
    for _ in range(F):
        k = draw(st.integers(1, 3))
        flows.append(tuple(draw(st.sampled_from(LINKS)) for _ in range(k)))
    return flows


@settings(max_examples=60, deadline=None)
@given(flow_sets(), st.floats(0.1, 10.0))
def test_maxmin_rates_respect_capacity_and_fair_share(flows, cap):
    rates = maxmin_rates(flows, cap)
    assert (rates > 0.0).all()
    per_link: dict = {}
    n_link: dict = {}
    for f, links in enumerate(flows):
        for l in set(links):
            per_link[l] = per_link.get(l, 0.0) + rates[f]
            n_link[l] = n_link.get(l, 0) + 1
    for l, total in per_link.items():
        assert total <= cap + 1e-6 * cap, (l, total, cap)
    for f, links in enumerate(flows):
        fair = min(cap / n_link[l] for l in set(links))
        assert rates[f] >= fair - 1e-6 * cap, (f, rates[f], fair)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10 ** 6),
       st.sampled_from(["hlp_ols", "heft", "cahlp_ols"]),
       st.floats(0.0, 2.0))
def test_fixed_latency_equals_default_engine_on_random_comm(seed, name, ccr):
    g = random_dag(seed, n=14)
    if ccr > 0 and g.num_edges:
        rng = np.random.default_rng(seed + 1)
        g = g.with_comm(ccr * float(g.proc.min(axis=1).mean())
                        * rng.uniform(0.2, 1.8, g.num_edges))
    mach = Machine.hybrid(4, 2)
    a = simulate(g, mach, make_scheduler(name), seed=seed)
    b = simulate(g, mach, make_scheduler(name), seed=seed,
                 network=make_network("fixed_latency"))
    assert a.makespan == b.makespan
    np.testing.assert_array_equal(a.schedule.start, b.schedule.start)


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10 ** 6), st.sampled_from(["hlp_ols", "heft"]))
def test_network_models_are_monotone(seed, name):
    g = random_dag(seed, n=12)
    if g.num_edges:
        rng = np.random.default_rng(seed + 1)
        g = g.with_comm(float(g.proc.min(axis=1).mean())
                        * rng.uniform(0.5, 2.0, g.num_edges))
    mach = Machine.hybrid(3, 2)
    ms = {n: simulate(g, mach, make_scheduler(name),
                      network=make_network(n)).makespan
          for n in ("instant", "fixed_latency", "maxmin_fair")}
    assert ms["instant"] <= ms["fixed_latency"] + 1e-9
    assert ms["fixed_latency"] <= ms["maxmin_fair"] + 1e-9


@st.composite
def transfer_sets(draw):
    """Random fixed-start transfer sets over the 3-type link pool."""
    T = draw(st.integers(1, 12))
    starts = [draw(st.floats(0.0, 6.0)) for _ in range(T)]
    sizes = [draw(st.one_of(st.just(0.0), st.floats(0.01, 5.0)))
             for _ in range(T)]
    ups = [draw(st.sampled_from(range(0, 6, 2))) for _ in range(T)]
    dns = [draw(st.sampled_from(range(1, 6, 2))) for _ in range(T)]
    return starts, sizes, ups, dns


@settings(max_examples=60, deadline=None)
@given(transfer_sets(), st.floats(0.2, 5.0))
def test_jitted_fluid_kernel_matches_numpy_oracle(ts, cap):
    """The jitted event kernel and the numpy reference solve the same
    fixed-start max-min fluid sub-problem to rtol 1e-6 (satellite of the
    whole-bucket contention fixpoint — ``fluid_finishes_jax`` is what the
    batched path runs per fixpoint round)."""
    import jax
    import jax.numpy as jnp

    from repro.sim.network import _fluid_finishes, fluid_finishes_jax

    starts, sizes, ups, dns = ts
    starts, sizes = np.asarray(starts), np.asarray(sizes)
    links = [(LINKS[u], LINKS[d]) for u, d in zip(ups, dns)]
    want = _fluid_finishes(starts, sizes, links, cap)
    with jax.enable_x64(True):
        got = np.asarray(fluid_finishes_jax(
            jnp.asarray(starts), jnp.asarray(sizes), jnp.asarray(ups),
            jnp.asarray(dns), jnp.ones(len(starts), bool), cap, len(LINKS)))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-9)


def test_contended_netbound_bucket_traces_once():
    """The whole contended netbound grid costs <= 1 contended-kernel
    compile (the ≤-1-per-bucket invariant extends to the fixpoint)."""
    from repro.sim.batch import (_delay_overrides, reset_trace_counts,
                                 trace_count)
    from repro.sim.scenarios import netbound_scenario

    net = make_network("maxmin_fair")
    items = []
    for i in range(3):
        sc = netbound_scenario(seed=700 + i)
        plan = make_scheduler("hlp_ols").allocate(sc.graph, sc.machine)
        items.append((sc.graph, plan))
    reset_trace_counts()
    _delay_overrides(items, [net] * len(items))
    assert trace_count("contended") <= 1
