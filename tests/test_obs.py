"""Unit tests for the ``repro.obs`` observability substrate.

Covers: the registry (enable/disable/capture, counters, spans, timers), the
chrome-trace export (required keys, Perfetto lane structure, round-trip
through the validating loader), per-task allocation provenance
(``DecisionRecord``, ``explain_divergence``), the trace-count shim
(``ValueError`` on unknown kinds, ``reset_trace_counts``), and — the
load-bearing invariant — **zero observer effect**: schedules and bucketed
sweeps must be bit-identical with the registry enabled or disabled.
"""
import json
import os

import numpy as np
import pytest

from repro import obs
from repro.sim import (NoiseModel, make_scheduler, reset_trace_counts,
                       simulate, trace_count)
from repro.sim.scenarios import default_suite, netbound_scenario


@pytest.fixture(autouse=True)
def _registry_off():
    """Every test starts and ends with the registry disabled and clean."""
    obs.disable()
    obs.reset(counters=True)
    yield
    obs.disable()
    obs.reset(counters=True)


# ------------------------------------------------------------------ registry
def test_registry_disabled_by_default_and_capture_restores():
    assert not obs.enabled()
    with obs.capture() as st:
        assert obs.enabled() and st.enabled
        with obs.capture():          # nested: still enabled afterwards
            pass
        assert obs.enabled()
    assert not obs.enabled()


def test_counters_always_on_and_resettable():
    obs.bump("x")
    obs.bump("x", 2)
    assert obs.counter_value("x") == 3 and obs.counters() == {"x": 3}
    obs.reset()                       # events only
    assert obs.counter_value("x") == 3
    obs.reset(counters=True)
    assert obs.counter_value("x") == 0


def test_span_is_noop_singleton_while_disabled():
    s1, s2 = obs.span("a"), obs.span("b")
    assert s1 is s2                   # the shared no-op: zero allocation
    with s1:
        pass
    assert obs.wall_events() == []
    with obs.capture():
        with obs.span("real", extra=1):
            pass
        (ev,) = obs.wall_events()
    assert ev["name"] == "real" and ev["args"] == {"extra": 1}
    assert ev["dur"] >= 0


def test_timer_measures_even_while_disabled():
    with obs.timer("t") as sp:
        x = sum(range(1000))
    assert x and sp.dur > 0 and sp.elapsed() >= sp.dur
    assert obs.wall_events() == []    # measured, not recorded


def test_gauges_and_snapshot():
    obs.set_gauge("g", 2.5)
    snap = obs.snapshot()
    assert snap["gauges"] == {"g": 2.5} and snap["enabled"] is False


# ---------------------------------------------------------- trace-count shim
def test_trace_count_rejects_unknown_kind_listing_valid_ones():
    with pytest.raises(ValueError, match="bucket, contended"):
        trace_count("nope")


def test_reset_trace_counts_zeroes_all_kinds():
    obs.bump("sim.compile.bucket", 3)
    obs.bump("sim.compile.contended", 1)
    reset_trace_counts()
    assert trace_count("bucket") == 0
    assert trace_count("contended") == 0


def test_compile_counters_work_under_capture():
    """The ≤-1-compile-per-bucket bookkeeping must be unaffected by spans
    and decision recording happening around it."""
    from repro.sim.batch import sample_actual_batch, bucketed_makespans

    sc = default_suite(seed=0)[0]
    plan = make_scheduler("hlp_ols").allocate(sc.graph, sc.machine)
    grid = sample_actual_batch(sc.graph, plan, NoiseModel(), [0])
    with obs.capture():
        reset_trace_counts()
        bucketed_makespans([(sc.graph, plan)], [grid])
        assert trace_count("bucket") <= 1
        first = trace_count("bucket")
        bucketed_makespans([(sc.graph, plan)], [grid])
        assert trace_count("bucket") == first   # cache hit: no retrace


# --------------------------------------------------------- observer effect
def _sched_fingerprint(res):
    s = res.schedule
    return (np.asarray(s.alloc).tobytes(), np.asarray(s.proc).tobytes(),
            np.asarray(s.start, np.float64).tobytes(),
            np.asarray(s.finish, np.float64).tobytes())


def test_zero_observer_effect_on_schedules_and_sweeps():
    """Golden invariant: enabling the registry changes *nothing* the
    algorithms compute — schedules and sweep arrays are bit-identical."""
    from repro.sim.batch import sample_actual_batch, bucketed_makespans

    suite = default_suite(seed=0)[:3]
    for sc in suite:
        for alg in ("hlp_ols", "heft", "er_ls"):
            off = simulate(sc.graph, sc.machine, make_scheduler(alg),
                           noise=NoiseModel("lognormal", 0.2), seed=sc.seed)
            with obs.capture():
                on = simulate(sc.graph, sc.machine, make_scheduler(alg),
                              noise=NoiseModel("lognormal", 0.2),
                              seed=sc.seed)
            assert off.makespan == on.makespan, (sc.name, alg)
            assert _sched_fingerprint(off) == _sched_fingerprint(on)
    sc = suite[0]
    plan = make_scheduler("hlp_ols").allocate(sc.graph, sc.machine)
    grid = sample_actual_batch(sc.graph, plan, NoiseModel("lognormal", 0.2),
                               [0, 1, 2])
    off = bucketed_makespans([(sc.graph, plan)], [grid])[0]
    with obs.capture():
        on = bucketed_makespans([(sc.graph, plan)], [grid])[0]
    np.testing.assert_array_equal(np.asarray(off), np.asarray(on))


# ------------------------------------------------------------- chrome traces
def test_sim_trace_export_round_trips_with_required_keys(tmp_path):
    sc = default_suite(seed=0)[1]
    res = simulate(sc.graph, sc.machine, make_scheduler("hlp_ols"))
    with obs.capture():
        with obs.span("lp.solve"):
            pass
        wall = obs.wall_trace_events()
    events = obs.sim_trace_events(res, sc.machine) + wall
    path = os.path.join(tmp_path, "trace.json")
    obs.export_chrome_trace(path, events)
    loaded = obs.load_chrome_trace(path)
    assert loaded, "export produced no events"
    for e in loaded:
        for k in obs.CHROME_REQUIRED_KEYS:
            assert k in e, (k, e)
    # every task emits >= 1 X event; lanes are per processor unit
    xs = [e for e in loaded if e["ph"] == "X" and e.get("cat") == "task"]
    assert len(xs) >= sc.graph.n
    total_units = sum(sc.machine.counts)
    assert {e["tid"] for e in xs} <= set(range(total_units))
    # the raw file is the chrome JSON-object form Perfetto expects
    with open(path) as f:
        doc = json.load(f)
    assert "traceEvents" in doc


def test_loader_rejects_events_missing_required_keys(tmp_path):
    path = os.path.join(tmp_path, "bad.json")
    with open(path, "w") as f:
        json.dump({"traceEvents": [{"ph": "X", "ts": 0, "name": "t"}]}, f)
    with pytest.raises(ValueError, match="pid"):
        obs.load_chrome_trace(path)


def test_wall_trace_lanes_group_by_span_family():
    with obs.capture():
        with obs.span("lp.solve"):
            pass
        with obs.span("lp.canonical_round"):
            pass
        with obs.span("sim.execute"):
            pass
        events = obs.wall_trace_events()
    lanes = {e["args"]["name"]: e["tid"] for e in events
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert set(lanes) == {"lp", "sim"}
    xs = [e for e in events if e["ph"] == "X"]
    assert {e["name"] for e in xs if e["tid"] == lanes["lp"]} == \
        {"lp.solve", "lp.canonical_round"}


def test_simulate_records_engine_spans_under_capture():
    sc = default_suite(seed=0)[2]
    with obs.capture():
        simulate(sc.graph, sc.machine, make_scheduler("hlp_ols"))
        names = {e["name"] for e in obs.wall_events()}
    assert "sim.allocate" in names and "sim.execute" in names
    assert "lp.assemble" in names and "lp.solve" in names


def test_canonical_round_is_spanned():
    from repro.core.hlp import solve_hlp

    sc = default_suite(seed=0)[2]
    m, k = sc.machine.counts
    with obs.capture():
        solve_hlp(sc.graph, m, k, canonical=True)
        names = {e["name"] for e in obs.wall_events()}
    assert "lp.canonical_round" in names


# --------------------------------------------------------------- provenance
def test_lp_decision_records_carry_fractional_x():
    sc = default_suite(seed=0)[2]
    with obs.capture():
        make_scheduler("hlp_ols").allocate(sc.graph, sc.machine)
        recs = obs.decision_records("hlp_ols")
    assert len(recs) == sc.graph.n
    assert all(r.x_frac is not None and r.tie_break for r in recs)
    assert {r.task for r in recs} == set(range(sc.graph.n))
    d = recs[0].to_dict()
    assert d["scheduler"] == "hlp_ols" and "x_frac" in d


def test_erls_decision_records_name_the_rule_fired():
    sc = default_suite(seed=0)[0]
    with obs.capture():
        simulate(sc.graph, sc.machine, make_scheduler("er_ls"))
        recs = obs.decision_records("er_ls")
    assert len(recs) == sc.graph.n
    assert all(r.rule in ("step1:gpu", "r2:cpu", "r2:gpu") for r in recs)


def test_explain_divergence_names_tasks_on_netbound():
    """Acceptance: the provenance diff explains >= 1 task where the
    comm-aware and oblivious LPs disagree on the netbound family."""
    sc = netbound_scenario(seed=300)
    diff = obs.explain_divergence(sc.graph, sc.machine,
                                  "cahlp_ols", "hlp_ols")
    assert diff, "cahlp_ols and hlp_ols agree everywhere on netbound?"
    for d in diff:
        assert {"task", "a", "b", "why"} <= set(d)
    # at least one divergent task must show a real comm price at stake
    assert any("comm paid" in d["why"] for d in diff)


def test_dump_decisions_writes_json(tmp_path):
    sc = default_suite(seed=0)[0]
    with obs.capture():
        make_scheduler("hlp_ols").allocate(sc.graph, sc.machine)
        path = os.path.join(tmp_path, "decisions.json")
        obs.dump_decisions(path)
    with open(path) as f:
        rows = json.load(f)
    assert len(rows) == sc.graph.n and rows[0]["scheduler"] == "hlp_ols"


# ------------------------------------------------------------------- streams
def test_stream_trace_has_task_and_link_lanes():
    from repro.sim import MaxMinFairNetwork, from_estee
    from repro.sim.engine import Machine
    from repro.streams import make_policy, replay_estee, run_stream

    fixture = os.path.join(os.path.dirname(__file__), "fixtures",
                           "estee_trace.json")
    machine = Machine.hybrid(4, 2)
    src = replay_estee([fixture] * 2, arrivals=[0.0, 1.0], seed=0)
    # the random policy mixes types, so dependences cross the boundary and
    # the tracker has transfers to log
    with obs.capture():
        res = run_stream(src, machine, make_policy("random"), seed=0,
                         network=MaxMinFairNetwork())
        assert obs.counter_value("stream.tasks_committed") == len(res.tasks)
    assert res.transfers, "contended stream should log transfers under obs"
    events = obs.stream_trace_events(res)
    xs = [e for e in events if e["ph"] == "X"]
    cats = {e["cat"] for e in xs}
    assert cats == {"task", "transfer"}
    # link lanes live *after* the unit lanes
    total_units = sum(machine.counts)
    xfer_tids = {e["tid"] for e in xs if e["cat"] == "transfer"}
    assert xfer_tids and min(xfer_tids) >= total_units
    sc = from_estee(fixture, counts=machine.counts, seed=0)
    assert sc.graph.has_comm


def test_stream_transfers_not_logged_while_disabled():
    from repro.sim import MaxMinFairNetwork
    from repro.sim.engine import Machine
    from repro.streams import make_policy, replay_estee, run_stream

    fixture = os.path.join(os.path.dirname(__file__), "fixtures",
                           "estee_trace.json")
    src = replay_estee([fixture], arrivals=[0.0], seed=0)
    res = run_stream(src, Machine.hybrid(4, 2), make_policy("heft"),
                     seed=0, network=MaxMinFairNetwork())
    assert res.transfers == ()
