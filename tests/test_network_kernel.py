"""The jitted contention kernel vs the numpy reference oracle.

The bucketed batch path prices ``maxmin_fair`` contention through a jitted
whole-bucket fixpoint (``batch.contended_bucket_delays`` on top of
``network.fluid_finishes_jax``); the per-plan numpy pair
(``contended_plan_delays`` / ``_fluid_finishes``) stays as the reference.
These tests pin the contract: the two agree to rtol 1e-6, the kernel costs
≤ 1 XLA compile per padded-shape envelope (and 0 on repeats), and the
switch validates its input.
"""
import numpy as np
import pytest

from repro.sim import (make_network, make_scheduler, plan_times,
                       set_contention_kernel)
from repro.sim.batch import (_delay_overrides, bucketed_makespans,
                             reset_trace_counts, trace_count)
from repro.sim.network import _fluid_finishes, fluid_finishes_jax
from repro.sim.scenarios import netbound_scenario

LINKS = [("up", 0), ("down", 0), ("up", 1), ("down", 1)]


def _random_transfer_set(rng, T):
    starts = rng.uniform(0.0, 5.0, T)
    sizes = rng.uniform(0.0, 4.0, T)
    sizes[rng.random(T) < 0.15] = 0.0        # some empty objects
    up = rng.integers(0, 2, T) * 2           # ("up", 0) or ("up", 1)
    dn = rng.integers(0, 2, T) * 2 + 1       # ("down", 0) or ("down", 1)
    return starts, sizes, up, dn


def _kernel_finishes(starts, sizes, up, dn, capacity):
    import jax
    import jax.numpy as jnp

    T = len(starts)
    with jax.enable_x64(True):
        fin = fluid_finishes_jax(jnp.asarray(starts), jnp.asarray(sizes),
                                 jnp.asarray(up), jnp.asarray(dn),
                                 jnp.ones(T, bool), capacity, len(LINKS))
        return np.asarray(fin)


@pytest.mark.parametrize("seed", range(8))
def test_fluid_kernel_matches_numpy_oracle_on_random_transfers(seed):
    rng = np.random.default_rng(seed)
    T = int(rng.integers(1, 14))
    cap = float(rng.uniform(0.5, 3.0))
    starts, sizes, up, dn = _random_transfer_set(rng, T)
    links = [(LINKS[u], LINKS[d]) for u, d in zip(up, dn)]
    want = _fluid_finishes(starts, sizes, links, cap)
    got = _kernel_finishes(starts, sizes, up, dn, cap)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-9)


def _netbound_items(n_scen=2):
    net = make_network("maxmin_fair")
    items, nets = [], []
    for i in range(n_scen):
        sc = netbound_scenario(seed=900 + i)
        for name in ("heft", "hlp_ols"):
            plan = make_scheduler(name).allocate(sc.graph, sc.machine)
            items.append((sc.graph, plan))
            nets.append(net)
    return items, nets


def test_contended_delays_match_oracle_on_netbound():
    items, nets = _netbound_items()
    jax_delays = _delay_overrides(items, nets)
    set_contention_kernel("numpy")
    try:
        np_delays = _delay_overrides(items, nets)
    finally:
        set_contention_kernel("jax")
    for jd, nd in zip(jax_delays, np_delays):
        np.testing.assert_allclose(jd, nd, rtol=1e-6, atol=1e-9)


def test_bucketed_makespans_agree_between_kernels():
    items, nets = _netbound_items()
    times = [np.tile(plan_times(g, plan, g.proc), (3, 1))
             for g, plan in items]
    ms_jax = bucketed_makespans(items, times, networks=nets)
    set_contention_kernel("numpy")
    try:
        ms_np = bucketed_makespans(items, times, networks=nets)
    finally:
        set_contention_kernel("jax")
    for a, b in zip(ms_jax, ms_np):
        np.testing.assert_allclose(a, b, rtol=1e-6)


def test_contended_kernel_traces_once_per_envelope():
    items, nets = _netbound_items()
    reset_trace_counts()
    _delay_overrides(items, nets)
    traced = trace_count("contended")
    assert traced <= 1, f"one netbound envelope should cost <= 1 compile, " \
                        f"got {traced}"
    _delay_overrides(items, nets)     # same shapes: cache hit, no retrace
    assert trace_count("contended") == traced


def test_set_contention_kernel_validates():
    with pytest.raises(ValueError, match="unknown contention kernel"):
        set_contention_kernel("tcp")
