"""Shared noise draws in ``sample_actual_batch``.

The noise grid of a plan is one draw per (noise, seeds, n), memoised and
shared across plans, then gathered on the plan's allocation in one
vectorised step.  The contract is that none of this is observable:

  (a) rows equal the per-row loop (``default_rng(seed)`` draw, full
      ``(n, Q)`` product, ``plan_times`` gather) bit-for-bit, for every
      noise kind, rigid and moldable plans, seeds as a list or an array;
  (b) the ``noise_draws.hits`` / ``.misses`` counters and
      ``PipelineStats.noise_hits`` / ``noise_misses`` account for every
      draw: one miss per (noise, seeds, n), a hit for every other plan;
  (c) a caller that writes to its rows cannot change the next call's;
  (d) the cache stays within its entry and byte bounds, and concurrent
      callers get the oracle's rows.
"""
import sys
import threading

import numpy as np
import pytest

from conftest import random_dag
from repro.core.dag import amdahl_speedup
from repro.obs import registry as _obs
from repro.sim import NoiseModel, make_scheduler
from repro.sim import batch
from repro.sim.batch import sample_actual_batch, sweep_suite_makespans
from repro.sim.engine import Machine, Plan, plan_times
from repro.sim.pipeline import (clear_plan_cache, last_pipeline_stats,
                                pipelined_sweep_makespans)
from repro.sim.scenarios import default_suite

NOISES = {"none": NoiseModel(),
          "zero_scale": NoiseModel("lognormal", 0.0),
          "lognormal": NoiseModel("lognormal", 0.2),
          "uniform": NoiseModel("uniform", 0.25)}


def _oracle(g, plan, noise, seeds):
    """The per-row loop, with the draw written out: one generator per
    seed, the full (n, Q) product, then the plan's column."""
    rows = []
    for s in seeds:
        rng = np.random.default_rng(int(s))
        if noise.kind == "lognormal" and noise.scale:
            actual = g.proc * rng.lognormal(-0.5 * noise.scale ** 2,
                                            noise.scale, size=g.n)[:, None]
        elif noise.kind == "uniform" and noise.scale:
            actual = g.proc * rng.uniform(1.0 - noise.scale,
                                          1.0 + noise.scale,
                                          size=g.n)[:, None]
        else:
            actual = g.proc
        rows.append(plan_times(g, plan, actual))
    return np.stack(rows)


def _plan(g, moldable: bool, seed: int = 0) -> Plan:
    """A hand-made plan: only ``alloc`` and ``width`` reach the grid."""
    rng = np.random.default_rng(seed)
    width = rng.integers(1, 4, size=g.n) if moldable else None
    return Plan(alloc=rng.integers(0, g.num_types, size=g.n),
                proc=np.zeros(g.n, dtype=np.int32), sequences={},
                width=width)


def _graph(moldable: bool, n: int = 23, seed: int = 5):
    g = random_dag(seed=seed, n=n, num_types=3)
    return g.with_speedup(amdahl_speedup(0.7, 3)) if moldable else g


def _counts():
    return (_obs.counter_value("noise_draws.hits"),
            _obs.counter_value("noise_draws.misses"))


@pytest.fixture(autouse=True)
def _empty_noise_cache():
    batch.clear_noise_cache()
    yield


# ------------------------------------------------------------- (a) parity
@pytest.mark.parametrize("seeds_as", ["list", "int64"])
@pytest.mark.parametrize("moldable", [False, True], ids=["rigid", "moldable"])
@pytest.mark.parametrize("kind", list(NOISES))
def test_rows_equal_the_per_row_loop(kind, moldable, seeds_as):
    g = _graph(moldable)
    plan = _plan(g, moldable)
    seeds = [3, 0, 2 ** 40 + 7, 11, 3]
    if seeds_as == "int64":
        seeds = np.asarray(seeds, dtype=np.int64)
    noise = NOISES[kind]
    want = _oracle(g, plan, noise, seeds)
    for _ in range(2):   # the second call reads the shared draw
        got = sample_actual_batch(g, plan, noise, seeds)
        assert got.shape == (5, g.n) and got.dtype == want.dtype
        np.testing.assert_array_equal(got, want)


def test_rows_follow_the_engine_stream():
    """Each row is ``NoiseModel.sample`` under the same generator: the
    event engine's realization of that seed."""
    g = _graph(False)
    plan = _plan(g, False)
    noise = NOISES["lognormal"]
    rows = sample_actual_batch(g, plan, noise, [8, 9])
    for s, row in zip([8, 9], rows):
        actual = noise.sample(g.proc, np.random.default_rng(s))
        np.testing.assert_array_equal(row, plan_times(g, plan, actual))


# ----------------------------------------------------------- (b) counters
def test_two_plans_on_one_graph_share_one_draw():
    g = _graph(False)
    noise = NOISES["lognormal"]
    h0, m0 = _counts()
    a = sample_actual_batch(g, _plan(g, False, 1), noise, [1, 2, 3])
    b = sample_actual_batch(g, _plan(g, False, 2), noise, [1, 2, 3])
    assert _counts() == (h0 + 1, m0 + 1)
    np.testing.assert_array_equal(b, _oracle(g, _plan(g, False, 2), noise,
                                             [1, 2, 3]))
    assert not np.array_equal(a, b)


@pytest.mark.parametrize("change", ["n", "seeds", "order", "scale", "kind"])
def test_a_different_key_draws_anew(change):
    g = _graph(False)
    noise, seeds = NOISES["lognormal"], [1, 2, 3]
    sample_actual_batch(g, _plan(g, False), noise, seeds)
    h0, m0 = _counts()
    if change == "n":
        g = _graph(False, n=g.n + 1)
    elif change == "seeds":
        seeds = [1, 2, 4]
    elif change == "order":
        seeds = [3, 2, 1]
    elif change == "scale":
        noise = NoiseModel("lognormal", 0.3)
    else:
        noise = NoiseModel("uniform", 0.2)
    plan = _plan(g, False)
    got = sample_actual_batch(g, plan, noise, seeds)
    assert _counts() == (h0, m0 + 1)
    np.testing.assert_array_equal(got, _oracle(g, plan, noise, seeds))


def test_noise_free_grids_touch_no_cache():
    g = _graph(True)
    h0, m0 = _counts()
    for kind in ("none", "zero_scale"):
        sample_actual_batch(g, _plan(g, True), NOISES[kind], [0, 1])
    assert _counts() == (h0, m0)
    assert not batch._noise_cache


# ------------------------------------------------------------- (c) copies
def test_writing_to_the_rows_leaves_the_next_call_alone():
    g = _graph(False)
    plan = _plan(g, False)
    noise = NOISES["uniform"]
    first = sample_actual_batch(g, plan, noise, [5, 6])
    want = first.copy()
    first[:] = -1.0
    np.testing.assert_array_equal(
        sample_actual_batch(g, plan, noise, [5, 6]), want)
    for mult in batch._noise_cache.values():
        assert not mult.flags.writeable


# ----------------------------------------------- (d) bounds, concurrency
def test_cache_keeps_its_entry_and_byte_bounds(monkeypatch):
    g = _graph(False)
    plan = _plan(g, False)
    noise = NOISES["lognormal"]
    for k in range(batch._NOISE_CACHE_ENTRIES + 3):
        sample_actual_batch(g, plan, noise, [k, k + 1])
    assert len(batch._noise_cache) == batch._NOISE_CACHE_ENTRIES
    # the least recently used goes first: a hit moves an entry to the back
    oldest = next(iter(batch._noise_cache))
    sample_actual_batch(g, plan, noise, np.frombuffer(oldest[3], np.int64))
    assert next(reversed(batch._noise_cache)) == oldest

    one_row = g.n * 8
    monkeypatch.setattr(batch, "_NOISE_CACHE_BYTES", 5 * one_row)
    batch.clear_noise_cache()
    for seeds in ([1, 2], [3, 4], [5, 6]):
        sample_actual_batch(g, plan, noise, seeds)
    assert sum(m.nbytes for m in batch._noise_cache.values()) <= 5 * one_row
    assert len(batch._noise_cache) == 2
    h0, m0 = _counts()
    big = list(range(6))            # larger than the whole budget
    for _ in range(2):
        got = sample_actual_batch(g, plan, noise, big)
    assert _counts() == (h0, m0 + 2)
    assert all(m.shape[0] == 2 for m in batch._noise_cache.values())
    np.testing.assert_array_equal(got, _oracle(g, plan, noise, big))


def test_concurrent_callers_get_the_oracle_rows():
    graphs = [_graph(False, n=n) for n in (17, 23)]
    noise = NOISES["lognormal"]
    seed_sets = [[1, 2, 3], [4, 5], [6]]
    cases = [(g, _plan(g, False, k), s) for g in graphs
             for k in range(2) for s in seed_sets]
    want = [_oracle(g, p, noise, s) for g, p, s in cases]
    errors: list[str] = []
    h0, m0 = _counts()

    def work(t):
        for r in range(20):
            i = (t + r) % len(cases)
            g, p, s = cases[i]
            if not np.array_equal(sample_actual_batch(g, p, noise, s),
                                  want[i]):
                errors.append(f"thread {t}, case {i}")

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(t,))
                   for t in range(8)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
        assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(old)
    assert not errors
    hits, misses = _counts()
    assert (hits - h0) + (misses - m0) == 8 * 20
    assert misses - m0 >= len(graphs) * len(seed_sets)


# ------------------------------------------------------------ the sweeps
def test_pipelined_sweep_equals_serial_and_counts_shared_draws():
    suite = default_suite(seed=0)[:4]
    entries = [(sc.graph, sc.machine, make_scheduler(a))
               for sc in suite for a in ("hlp_ols", "heft")]
    noise, seeds = NOISES["lognormal"], np.arange(1, 6, dtype=np.int64)
    serial = sweep_suite_makespans(entries, noise=noise, seeds=seeds)
    batch.clear_noise_cache()
    clear_plan_cache()
    piped = pipelined_sweep_makespans(entries, noise=noise, seeds=seeds)
    for a, b in zip(serial, piped):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    sizes = {g.n for g, _, _ in entries}
    stats = last_pipeline_stats()
    assert stats.noise_misses == len(sizes)
    assert stats.noise_hits == len(entries) - len(sizes)
    # the same seeds again: every grid is shared with the earlier call
    pipelined_sweep_makespans(entries, noise=noise, seeds=seeds)
    stats = last_pipeline_stats()
    assert (stats.noise_hits, stats.noise_misses) == (len(entries), 0)


def test_sweep_with_a_sampler_of_its_own_counts_its_draws():
    """The benchmark's shape: a clean row plus noisy rows per plan, drawn
    through ``sample_fn``; two sizes, four plans each."""
    machine = Machine((4, 2))
    graphs = [random_dag(seed=s, n=n) for n in (12, 19) for s in (1, 2)]
    entries = [(g, machine, make_scheduler(a))
               for g in graphs for a in ("hlp_ols", "heft")]
    noise, seeds = NOISES["lognormal"], np.arange(7, 12, dtype=np.int64)

    def sample(g, plan):
        return np.vstack([sample_actual_batch(g, plan, NoiseModel(), [0]),
                          sample_actual_batch(g, plan, noise, seeds)])

    pipelined_sweep_makespans(entries, sample_fn=sample)
    stats = last_pipeline_stats()
    assert (stats.noise_hits, stats.noise_misses) == (6, 2)
