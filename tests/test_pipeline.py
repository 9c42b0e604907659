"""Determinism and invariants of the pipelined campaign executor.

The executor's contract (``repro.sim.pipeline``) is that *none* of its
machinery — worker pools, the content-addressed plan cache, envelope
bucketing with dispatch-on-close — is observable in the results:

  (a) ``pipelined_sweep_makespans`` equals the serial
      ``sweep_suite_makespans`` bit-for-bit, for any ``workers`` and any
      cache setting (golden SHA-256 plan hashes + array equality, plus a
      hypothesis property over random grids);
  (b) the per-entry network grid (how the campaign's netbound sub-grid is
      phrased) matches the per-network serial sweeps — including the
      contended ``maxmin_fair`` pricing;
  (c) compile counts stay pinned: <= 1 XLA trace per envelope bucket, and
      a repeated identical sweep traces nothing new;
  (d) cache hits return the *same* ``Plan`` object and the hit/miss
      counters account for every cacheable allocation.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.obs import registry as _obs
from repro.sim import NoiseModel, make_scheduler
from repro.sim.batch import (search_envelope, sweep_suite_makespans,
                             trace_count)
from repro.sim.pipeline import (build_plans, cached_allocate, cached_solve,
                                clear_plan_cache, configure_xla_cache,
                                graph_fingerprint, last_pipeline_stats,
                                pipelined_sweep_makespans, plan_cache_key,
                                plan_fingerprint, plan_workers)
from repro.sim.scenarios import default_suite, netbound_scenario

NOISE = NoiseModel("lognormal", 0.2)
SEEDS = [0, 1, 2]


def _entries(n_sc=4, algs=("hlp_ols", "heft")):
    suite = default_suite(seed=0)[:n_sc]
    return [(sc.graph, sc.machine, make_scheduler(a))
            for sc in suite for a in algs]


# ------------------------------------------------------------------ parity
def test_pipelined_equals_serial_for_workers_and_cache():
    entries = _entries()
    serial = sweep_suite_makespans(entries, noise=NOISE, seeds=SEEDS)
    for kw in ({"workers": 1, "cache": False},
               {"workers": 1, "cache": True},
               {"workers": 4, "cache": True}):
        clear_plan_cache()
        piped = pipelined_sweep_makespans(entries, noise=NOISE, seeds=SEEDS,
                                          **kw)
        assert len(piped) == len(serial)
        for a, b in zip(serial, piped):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b)), kw


def test_batch_entrypoint_routes_through_pipeline():
    entries = _entries(n_sc=2)
    serial = sweep_suite_makespans(entries, noise=NOISE, seeds=SEEDS)
    clear_plan_cache()
    routed = sweep_suite_makespans(entries, noise=NOISE, seeds=SEEDS,
                                   workers=2, cache=True)
    for a, b in zip(serial, routed):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_plan_golden_hashes_bit_identical_across_workers():
    entries = _entries()
    direct = [sched.allocate(g, m) for g, m, sched in _entries()]
    golden = [plan_fingerprint(p) for p in direct]
    assert all(len(h) == 64 for h in golden)   # sha256 hex
    for workers in (1, 4):
        clear_plan_cache()
        plans, build_s = build_plans(entries, workers=workers, cache=True)
        assert [plan_fingerprint(p) for p in plans] == golden
        assert build_s >= 0.0


def test_random_grid_parity_property():
    pytest.importorskip("hypothesis")  # dev extra: requirements-dev.txt
    from hypothesis import given, settings, strategies as st

    from conftest import random_dag
    from repro.sim.engine import Machine

    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 10 ** 6))
    def prop(seed):
        rng = np.random.default_rng(seed)
        entries = []
        for i in range(3):
            g = random_dag(seed + i, n=int(rng.integers(6, 16)), p_edge=0.3)
            m = Machine.from_counts([int(rng.integers(2, 5)),
                                     int(rng.integers(1, 3))])
            entries.append((g, m, make_scheduler("heft")))
        serial = sweep_suite_makespans(entries, noise=NOISE, seeds=[0, 1])
        clear_plan_cache()
        piped = pipelined_sweep_makespans(entries, noise=NOISE, seeds=[0, 1],
                                          workers=2)
        for a, b in zip(serial, piped):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    prop()


# ----------------------------------------------------------- network grid
def test_per_entry_network_grid_matches_serial_sweeps():
    """The campaign's netbound phrasing: one flat entry per (alloc, net),
    the cache collapsing the repeated allocations — must equal the
    per-network serial sweeps, contended model included."""
    from repro.sim.adapters import CommAwareHLPScheduler
    from repro.sim.network import make_network

    sc = netbound_scenario(seed=0)
    nets = [make_network(n)
            for n in ("instant", "fixed_latency", "maxmin_fair")]
    mks = [lambda: make_scheduler("hlp_ols"),
           lambda: CommAwareHLPScheduler(contention=True)]
    entries, networks = [], []
    for mk in mks:
        for net in nets:
            entries.append((sc.graph, sc.machine, mk()))
            networks.append(net)
    clear_plan_cache()
    piped = pipelined_sweep_makespans(entries, noise=NOISE, seeds=[0, 1],
                                      networks=networks, workers=1)
    stats = last_pipeline_stats()
    assert stats.cache_hits == 4      # 2 allocs x 3 nets -> 2 solves + 4 hits
    assert stats.cache_misses == 2
    for j, (mk, _) in enumerate([(m, None) for m in mks]):
        for k, net in enumerate(nets):
            serial = sweep_suite_makespans(
                [(sc.graph, sc.machine, mk())],
                noise=NOISE, seeds=[0, 1], network=net)
            np.testing.assert_array_equal(np.asarray(piped[j * 3 + k]),
                                          np.asarray(serial[0]))


# ---------------------------------------------------------- compile counts
def test_envelope_compile_pin_and_no_retrace():
    entries = _entries()
    envelopes = {search_envelope(g, m) for g, m, _ in entries}
    clear_plan_cache()
    t0 = trace_count("bucket")
    pipelined_sweep_makespans(entries, noise=NOISE, seeds=SEEDS)
    t1 = trace_count("bucket")
    assert t1 - t0 <= len(envelopes)   # <= 1 XLA trace per envelope bucket
    assert last_pipeline_stats().buckets == len(envelopes)
    pipelined_sweep_makespans(entries, noise=NOISE, seeds=SEEDS)
    assert trace_count("bucket") == t1   # repeat sweep: zero new traces


def test_overlap_is_measured():
    entries = _entries()
    clear_plan_cache()
    pipelined_sweep_makespans(entries, noise=NOISE, seeds=SEEDS)
    stats = last_pipeline_stats()
    assert stats.plans == len(entries)
    assert stats.buckets >= 2
    assert stats.total_s > 0
    # >= 2 buckets: host work (sampling/bucket building) necessarily runs
    # after the first async dispatch, so measured overlap is strictly > 0
    assert stats.overlap_frac > 0
    assert stats.cache_hits + stats.cache_misses == len(entries)


# -------------------------------------------------------------- plan cache
def test_cache_hit_returns_same_plan_object_and_counts():
    sc = default_suite(seed=0)[0]
    sched = make_scheduler("hlp_ols")
    clear_plan_cache()
    h0, m0 = (_obs.counter_value("plan_cache.hits"),
              _obs.counter_value("plan_cache.misses"))
    p1 = cached_allocate(sched, sc.graph, sc.machine)
    p2 = cached_allocate(make_scheduler("hlp_ols"), sc.graph, sc.machine)
    assert p2 is p1   # zero observer effect: the very same Plan object
    assert _obs.counter_value("plan_cache.misses") - m0 == 1
    assert _obs.counter_value("plan_cache.hits") - h0 == 1
    clear_plan_cache()
    p3 = cached_allocate(make_scheduler("hlp_ols"), sc.graph, sc.machine)
    assert plan_fingerprint(p3) == plan_fingerprint(p1)


def test_uncacheable_schedulers_bypass_the_cache():
    from repro.sim.adapters import FrozenPlanScheduler

    sc = default_suite(seed=0)[0]
    online = make_scheduler("er_ls")   # allocate() binds state -> None
    assert plan_cache_key(sc.graph, sc.machine, online) is None
    plan = make_scheduler("hlp_ols").allocate(sc.graph, sc.machine)
    frozen = FrozenPlanScheduler(plan, name="hlp_ols")
    assert plan_cache_key(sc.graph, sc.machine, frozen) is None
    clear_plan_cache()
    m0 = _obs.counter_value("plan_cache.misses")
    assert cached_allocate(frozen, sc.graph, sc.machine) is plan
    assert _obs.counter_value("plan_cache.misses") == m0   # never counted


def test_cached_solve_dedupes_named_builders():
    sc = default_suite(seed=0)[0]
    calls = []

    def build():
        calls.append(1)
        return make_scheduler("heft").allocate(sc.graph, sc.machine)

    clear_plan_cache()
    p1 = cached_solve("test.build", sc.graph, sc.machine, build)
    p2 = cached_solve("test.build", sc.graph, sc.machine, build)
    assert p2 is p1 and len(calls) == 1
    p3 = cached_solve("test.build", sc.graph, sc.machine, build,
                      extra=("other",))
    assert len(calls) == 2 and p3 is not None


def test_graph_fingerprint_is_content_addressed():
    a, b = default_suite(seed=0)[0], default_suite(seed=0)[0]
    assert a.graph is not b.graph
    assert graph_fingerprint(a.graph) == graph_fingerprint(b.graph)
    other = default_suite(seed=0)[1]
    assert graph_fingerprint(a.graph) != graph_fingerprint(other.graph)


# ------------------------------------------------------------------- knobs
def test_plan_workers_env_knob(monkeypatch):
    monkeypatch.setenv("REPRO_PLAN_WORKERS", "3")
    assert plan_workers() == 3
    monkeypatch.setenv("REPRO_PLAN_WORKERS", "0")
    assert plan_workers() == 1
    monkeypatch.delenv("REPRO_PLAN_WORKERS")
    assert plan_workers() >= 1


def test_process_pool_parity(monkeypatch):
    """LP-heavy adapters through the persistent process pool: bit-identical
    plans, and the pool must survive (no broken-pool fallback) under the
    guarded pytest ``__main__``."""
    entries = _entries(n_sc=2, algs=("hlp_est",))
    golden = [plan_fingerprint(s.allocate(g, m)) for g, m, s in
              _entries(n_sc=2, algs=("hlp_est",))]
    monkeypatch.setenv("REPRO_PLAN_POOL", "process")
    broken0 = _obs.counter_value("plan_pool.broken")
    clear_plan_cache()
    plans, _ = build_plans(entries, workers=2, cache=False)
    assert [plan_fingerprint(p) for p in plans] == golden
    assert _obs.counter_value("plan_pool.broken") == broken0


def test_process_pool_workers_stay_on_cpu():
    """A parent that holds an accelerator must never see its plan workers
    initialize one: with ``JAX_PLATFORMS`` naming the TPU in the parent's
    environment, a fresh pool's worker still comes up on the CPU backend."""
    code = ("import jax\n"
            "from repro.sim.pipeline import _process_pool\n"
            "if __name__ == '__main__':\n"
            "    print(_process_pool(1).submit(jax.default_backend).result())\n")
    env = dict(os.environ, JAX_PLATFORMS="tpu", PYTHONPATH="src")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=120,
                          cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.split()[-1] == "cpu"


@pytest.mark.parametrize("case", ["env_dir_stands", "fixed_default",
                                  "same_path_every_call"])
def test_configure_xla_cache(case, tmp_path, monkeypatch):
    import jax

    from repro.sim.pipeline import XLA_CACHE_DIR

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    old = {k: getattr(jax.config, k) for k in keys}
    try:
        if case == "env_dir_stands":
            target = os.path.join(str(tmp_path), "xla")
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", target)
            assert configure_xla_cache() == target
            # JAX's own handling of the variable stands: no other directory
            assert jax.config.jax_compilation_cache_dir == \
                old["jax_compilation_cache_dir"]
        elif case == "fixed_default":
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            path = configure_xla_cache()
            root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            assert path == XLA_CACHE_DIR == os.path.join(root, ".jax_cache")
            assert os.path.isdir(path)
            assert jax.config.jax_compilation_cache_dir == path
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            assert configure_xla_cache() == configure_xla_cache() \
                == XLA_CACHE_DIR
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    finally:
        for k, v in old.items():
            jax.config.update(k, v)
