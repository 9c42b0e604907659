"""Moldable (multi-width) tasks through every layer — deterministic checks.

The Allocation-API-v2 contract beyond width-1 parity:

  * speedup-curve invariants are enforced at construction;
  * the width-indexed MHLP relaxation equals HLP/QHLP on width-1 tables and
    only gains from widths; its rounded decisions are in range;
  * width-aware schedulers (LS/OLS, HEFT, ER-LS, EFT) produce feasible
    schedules — validated with the width-capacity invariants — that respect
    the universal lower bound;
  * the engine, the bucketed one-jit batch path and the streams layer agree
    on moldable plans (engine↔batch rtol 1e-5, ≤ 1 XLA compile per bucket);
  * the campaign claim: width-aware MHLP beats its width-1 restriction on
    mean makespan over the ``moldable_cholesky`` family, through the
    bucketed path, with the compile count asserted.
"""
import numpy as np
import pytest

from repro.core import (CPU, GPU, TaskGraph, amdahl_speedup, powerlaw_speedup,
                        efficient_width, erls_decide, erls_decide_moldable,
                        heft, hlp_ols, list_schedule, makespan_lower_bound,
                        solve_hlp, solve_mhlp)
from repro.platform import (Decision, PLATFORMS, Platform, PoolState,
                            as_decision, as_platform, decisions_of,
                            pack_decisions)
from repro.sim import (Machine, NoiseModel, make_scheduler, moldable_suite,
                       simulate)
from repro.sim import batch
from conftest import random_dag


def _moldable_dag(seed=0, n=16, W=4, p_edge=0.2):
    g = random_dag(seed, n=n, p_edge=p_edge)
    rng = np.random.default_rng(seed + 100)
    return g.with_speedup(amdahl_speedup(rng.uniform(0.5, 0.95, g.n), W))


# ------------------------------------------------------------ curve algebra
def test_speedup_curve_invariants():
    a = amdahl_speedup(0.8, 6)
    assert a.shape == (1, 6) and a[0, 0] == 1.0
    assert (np.diff(a) >= 0).all()
    eff = a / np.arange(1, 7)
    assert (np.diff(eff) <= 1e-12).all()
    p = powerlaw_speedup([0.0, 0.5, 1.0], 4)
    assert np.allclose(p[0], 1.0)            # γ=0: no speedup
    assert np.allclose(p[2], [1, 2, 3, 4])   # γ=1: linear


def test_bad_curves_rejected():
    g = random_dag(0, n=5)
    with pytest.raises(ValueError):          # width-1 point must be 1
        g.with_speedup(np.full((5, 2), 2.0))
    with pytest.raises(ValueError):          # decreasing speedup
        g.with_speedup(np.tile([1.0, 0.5], (5, 1)))
    with pytest.raises(ValueError):          # super-linear speedup
        g.with_speedup(np.tile([1.0, 3.0], (5, 1)))
    with pytest.raises(ValueError):          # wrong row count
        g.with_speedup(np.ones((4, 2)))


def test_proc_w_and_moldable_times():
    g = _moldable_dag(seed=1, n=8, W=3)
    alloc = (np.arange(8) % 2).astype(np.int32)
    width = np.asarray([1, 2, 3, 1, 2, 3, 1, 2])
    t = g.moldable_times(alloc, width)
    for j in range(8):
        assert t[j] == pytest.approx(
            g.proc[j, alloc[j]] / g.speedup[j, width[j] - 1])
        assert g.proc_w(j, 0, 1) == g.proc[j, 0]
    with pytest.raises(ValueError):
        g.moldable_times(alloc, np.full(8, 9))   # width beyond the table


# ------------------------------------------------------- Platform / Decision
def test_platform_and_decision_basics():
    p = Platform.hybrid(8, 2)
    assert p.names == ("cpu", "gpu") and p.to_counts() == [8, 2]
    assert Platform((4,)).names == ("cpu",)
    assert Platform((4, 2, 1)).names == ("cpu", "gpu1", "gpu2")
    assert as_platform(p) is p
    with pytest.warns(DeprecationWarning):
        assert as_platform([8, 2]).counts == (8, 2)
    assert as_decision(1) == Decision(1, 1)
    assert as_decision((0, 3)) == Decision(0, 3)
    with pytest.raises(ValueError):
        Decision(0, 0)
    alloc, width = pack_decisions(decisions_of([0, 1, 0], [1, 2, 3]))
    np.testing.assert_array_equal(alloc, [0, 1, 0])
    np.testing.assert_array_equal(width, [1, 2, 3])
    for name, plat in PLATFORMS.items():
        assert plat.num_types == len(plat.names)


def test_pool_state_wide_commits():
    st = PoolState(Platform((3,)))
    pids, s, f = st.commit_wide(0, 0.0, 2.0, 2)     # claim units 0,1
    assert len(pids) == 2 and s == 0.0 and f == 2.0
    assert st.earliest_idle(0) == 0.0               # unit 2 still idle
    assert st.earliest_idle(0, 2) == 2.0            # 2 units only at t=2
    assert st.earliest_idle(0, 4) == np.inf         # never fits
    with pytest.raises(RuntimeError):
        st.commit_wide(0, 0.0, 1.0, 4)


# ----------------------------------------------------------------- MHLP LP
def test_mhlp_equals_hlp_on_width1_tables():
    g = random_dag(3, n=12)
    g1 = g.with_speedup(np.ones((g.n, 1)))
    v_m = solve_mhlp(g1, Platform.hybrid(4, 2)).lp_value
    v_h = solve_hlp(g, 4, 2).lp_value
    assert v_m == pytest.approx(v_h, rel=1e-6)


def test_mhlp_widths_only_help_the_relaxation():
    g = _moldable_dag(seed=4, n=12)
    p = Platform.hybrid(4, 2)
    v_m = solve_mhlp(g, p)
    v_1 = solve_hlp(g, 4, 2)
    assert v_m.lp_value <= v_1.lp_value + 1e-9
    assert (v_m.width >= 1).all() and (v_m.width <= g.max_width).all()
    counts = np.asarray(p.to_counts())
    assert (v_m.width <= counts[v_m.alloc]).all()
    assert all(d == Decision(int(q), int(w))
               for d, q, w in zip(v_m.decisions, v_m.alloc, v_m.width))


def test_mhlp_objective_finite_with_type_restricted_tasks():
    """Regression: a task that cannot run on one type (inf entry) must not
    poison the fractional MHLP objective with NaN — the exact and the
    first-order solvers both return finite λ, and the canonical rounding's
    λ budget stays usable."""
    from repro.core.hlp_jax import solve_mhlp_jax

    proc = np.array([[4.0, 1.0], [3.0, np.inf], [4.0, 1.0]])
    curve = np.tile([1.0, 1.8], (3, 1))
    g = TaskGraph.build(proc, [(0, 1), (1, 2)], speedup=curve)
    p = Platform.hybrid(2, 2)
    exact = solve_mhlp(g, p)
    approx = solve_mhlp_jax(g, p, iters=200)
    assert np.isfinite(exact.lp_value) and np.isfinite(approx.lp_value)
    assert approx.lp_value >= exact.lp_value - 1e-9
    assert exact.alloc[1] == CPU                  # the restricted task
    can = solve_mhlp(g, p, canonical=True)
    assert np.isfinite(can.lp_value) and can.alloc[1] == CPU
    hlp_ols(g, p, can.alloc, can.width).validate(g, p)


def test_canonical_moldable_rounding_is_deterministic():
    g = _moldable_dag(seed=5, n=10)
    p = Platform.hybrid(4, 2)
    a = solve_mhlp(g, p, canonical=True)
    b = solve_mhlp(g, p, canonical=True)
    np.testing.assert_array_equal(a.alloc, b.alloc)
    np.testing.assert_array_equal(a.width, b.width)
    sched = hlp_ols(g, p, a.alloc, a.width)
    sched.validate(g, p)


# ------------------------------------------------------ width-aware schedule
def test_width_aware_list_schedule_validates():
    g = _moldable_dag(seed=6, n=18)
    p = Platform.hybrid(5, 3)
    sol = solve_mhlp(g, p)
    sched = hlp_ols(g, p, sol.alloc, sol.width)
    sched.validate(g, p)
    assert sched.makespan >= makespan_lower_bound(g, p.to_counts()) - 1e-9
    # width capacity is enforced
    with pytest.raises(ValueError):
        list_schedule(g, p, np.zeros(g.n, np.int32), width=np.full(g.n, 6))


def test_wide_task_claims_that_many_units():
    # 3 independent tasks on one 4-unit pool: a width-4 task, then two
    # width-2 tasks run side by side after it.
    proc = np.full((3, 1), 4.0)
    curve = np.stack([np.array([1, 2, 3, 4.0])] * 3)
    g = TaskGraph.build(proc, [], speedup=curve)
    sched = list_schedule(g, Platform((4,)), np.zeros(3, np.int32),
                          priority=np.array([3.0, 2.0, 1.0]),
                          width=np.array([4, 2, 2]))
    sched.validate(g, Platform((4,)))
    assert sched.start[0] == 0.0 and sched.finish[0] == 1.0
    assert sched.start[1] == sched.start[2] == 1.0   # parallel pair
    assert sched.makespan == pytest.approx(3.0)
    assert sorted(sched.procs_of(1) + sched.procs_of(2)) == [0, 1, 2, 3]


def test_narrow_tasks_backfill_around_blocked_wide_task():
    # Pool of 2; a width-2 task is blocked while unit 0 is busy — the
    # lower-priority width-1 task must backfill onto idle unit 1.
    proc = np.array([[2.0], [2.0], [1.0]])
    curve = np.stack([np.array([1.0, 2.0])] * 3)
    g = TaskGraph.build(proc, [], speedup=curve)
    sched = list_schedule(g, Platform((2,)), np.zeros(3, np.int32),
                          priority=np.array([3.0, 2.0, 1.0]),
                          width=np.array([1, 2, 1]))
    sched.validate(g, Platform((2,)))
    assert sched.start[2] == 0.0          # backfilled beside task 0
    assert sched.start[1] == 2.0          # wide task waits for both units


def test_moldable_heft_erls_eft_feasible_and_no_worse():
    g = _moldable_dag(seed=7, n=20)
    p = Platform.hybrid(6, 3)
    rigid = TaskGraph.build(g.proc, [tuple(e) for e in g.edges],
                            comm=g.comm)
    for fn in (heft,):
        wide = fn(g, p)
        wide.validate(g, p)
        assert wide.width is not None and wide.width.max() > 1
        assert wide.makespan <= fn(rigid, p).makespan + 1e-9


def test_erls_moldable_rule_reduces_at_width1():
    rng = np.random.default_rng(0)
    for _ in range(50):
        pc, pg = rng.uniform(0.1, 10, 2)
        m, k = int(rng.integers(2, 16)), int(rng.integers(1, 4))
        r = rng.uniform(0, 5)
        d = erls_decide_moldable(pc, pg, m, k, r, 1, 1)
        assert d == Decision(erls_decide(pc, pg, m, k, r), 1)


def test_efficient_width_respects_floor_and_pool():
    g = _moldable_dag(seed=8, n=6, W=4)
    for j in range(g.n):
        w = efficient_width(g, j, 4, eff_floor=0.5)
        assert 1 <= w <= 4
        assert g.speedup[j, w - 1] / w >= 0.5 - 1e-12
        assert efficient_width(g, j, 1) == 1
    assert efficient_width(random_dag(0, n=3), 0, 8) == 1   # no curves


# ------------------------------------------------------- engine/batch/stream
def test_engine_simulates_moldable_adapters():
    """Every width-aware adapter runs through ``simulate`` (validation on),
    and trace events carry the decision widths."""
    sc = moldable_suite(seed=0, num=1)[0]
    for name in ("mhlp_ols", "heft", "er_ls", "eft"):
        r = simulate(sc.graph, sc.machine, make_scheduler(name),
                     noise=NoiseModel("lognormal", 0.2), seed=3, trace=True)
        widths = [e.width for e in r.trace if e.event == "start"]
        assert len(widths) == sc.graph.n and min(widths) >= 1
    # the moldable planner actually allocates widths on this family
    plan = make_scheduler("mhlp_ols").allocate(sc.graph, sc.machine)
    assert plan.width is not None and plan.width.max() > 1


def test_machine_names_are_unified():
    """Satellite fix: unnamed constructions get the canonical type labels,
    matching ``Machine.hybrid`` — one naming through ``Platform``."""
    assert Machine((8, 2)).names == Machine.hybrid(8, 2).names == ("cpu", "gpu")
    for sc in moldable_suite(seed=0, num=1):
        assert sc.machine.names == ("cpu", "gpu")
    assert Machine((1, 2, 3)).names == ("cpu", "gpu1", "gpu2")


def test_moldable_batch_path_matches_engine():
    noise = NoiseModel("lognormal", 0.2)
    seeds = list(range(6))
    sc = moldable_suite(seed=1, num=1)[0]
    for name in ("mhlp_ols", "heft"):
        ms = batch.sweep_makespans(sc.graph, sc.machine, make_scheduler(name),
                                   noise=noise, seeds=seeds)
        ref = [simulate(sc.graph, sc.machine, make_scheduler(name),
                        noise=noise, seed=s).makespan for s in seeds]
        np.testing.assert_allclose(ms, ref, rtol=1e-5)


# --------------------------------------------------------- the campaign win
def test_width_aware_mhlp_beats_width1_restriction_bucketed():
    """The acceptance claim: on the checked-in ``moldable_cholesky`` family
    the width-aware MHLP beats its width-1 restriction (hlp_ols on the
    identical graphs) on mean makespan, evaluated through the bucketed
    ≤-1-compile-per-bucket JAX path — compile count asserted."""
    noise = NoiseModel("lognormal", 0.2)
    seeds = list(range(6))
    suite = moldable_suite(seed=0, num=3)
    entries = [(sc.graph, sc.machine, make_scheduler(name))
               for sc in suite for name in ("mhlp_ols", "hlp_ols")]
    items = [(g, s.allocate(g, m)) for g, m, s in entries]
    n_buckets = len(batch.bucket_plans(items))
    batch.reset_trace_counts()
    out = batch.sweep_suite_makespans(entries, noise=noise, seeds=seeds)
    compiles = batch.trace_count("bucket")
    assert compiles <= n_buckets, (compiles, n_buckets)
    mold = np.mean([out[i].mean() for i in range(0, len(out), 2)])
    w1 = np.mean([out[i].mean() for i in range(1, len(out), 2)])
    assert mold < w1, (mold, w1)
    # and the margin is structural, not noise
    assert w1 / mold > 1.2, (mold, w1)


def test_streams_handle_moldable_jobs():
    from repro.streams import JobFactory, PoissonProcess, make_policy, \
        open_stream, run_stream

    src = open_stream(PoissonProcess(0.05),
                      JobFactory(("moldable_cholesky",)), num_jobs=4,
                      num_tenants=2, seed=0)
    res = run_stream(src, Machine.hybrid(8, 4), make_policy("mhlp_ols"),
                     noise=NoiseModel("lognormal", 0.1), seed=0)
    assert len(res.jobs) == 4
    assert max(t.width for t in res.tasks) > 1     # widths actually used
    assert (res.utilization() <= 1.0 + 1e-9).all()
    sd = res.slowdowns()
    assert (sd >= 1.0 - 1e-9).all()


def test_dispatcher_logs_first_class_decisions():
    from repro.serve.dispatch import ERLSDispatcher, Pool, Request, \
        token_cost_model

    d = ERLSDispatcher(Pool("cpu", 8), Pool("gpu", 2, speed=4.0),
                       token_cost_model(pool_flops={"cpu": 1e11, "gpu": 1e12}))
    d.submit(Request(0, 512, 128, 0.0))
    d.submit(Request(1, 2048, 64, 0.1))
    assert len(d.decisions) == 4                   # 2 requests × 2 phases
    assert all(isinstance(dec, Decision) for _, _, dec in d.decisions)
    assert all(p.width == 1 for p in d.log)        # serving stays rigid
