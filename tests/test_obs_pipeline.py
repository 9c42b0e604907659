"""The pipeline's spans and counters, and spans returned from pool workers.

* The dispatch loop's three parts (``sim.pipeline.sample``, ``.assemble``,
  ``.launch``) are each one timer: a span's duration is exactly its
  ``PipelineStats`` counter, and the parts add up to ``dispatch_s``, which
  is the ``sim.pipeline.dispatch`` span.
* With the registry enabled, ``build_plans`` keeps its thread and process
  pools; the spans and decision records made in a worker come back with
  its plan, tagged with the call, inside the parent's build span (one
  clock), and in submission order: the same records as a serial build.
* Plans are the same with the registry on or off.
"""
from contextlib import nullcontext

import pytest

from repro import obs
from repro.obs import registry as _obs
from repro.sim import NoiseModel, make_scheduler
from repro.sim import pipeline
from repro.sim.pipeline import (build_plans, clear_plan_cache,
                                last_pipeline_stats,
                                pipelined_sweep_makespans, plan_fingerprint)
from repro.sim.scenarios import default_suite

NOISE = NoiseModel("lognormal", 0.2)
PARTS = {"sim.pipeline.sample": "sample_s",
         "sim.pipeline.assemble": "assemble_s",
         "sim.pipeline.launch": "launch_s"}


@pytest.fixture(autouse=True)
def _registry_off():
    obs.disable()
    obs.reset(counters=True)
    yield
    obs.disable()
    obs.reset(counters=True)


def _entries(algs=("hlp_ols", "hlp_jax_ols", "heft"), n_sc=4):
    return [(sc.graph, sc.machine, make_scheduler(a))
            for sc in default_suite(seed=0)[:n_sc] for a in algs]


def _by_name(events, name):
    return [e for e in events if e["name"] == name]


# ------------------------------------------------------- the dispatch loop
@pytest.mark.parametrize("recording", [False, True])
def test_dispatch_parts_add_up_to_dispatch_s(recording):
    entries = _entries(algs=("hlp_ols", "heft"))
    with obs.capture() if recording else nullcontext():
        pipelined_sweep_makespans(entries, noise=NOISE, seeds=range(256),
                                  workers=1)
        events = obs.wall_events()
    st = last_pipeline_stats()
    parts = st.sample_s + st.assemble_s + st.launch_s
    assert min(st.sample_s, st.assemble_s, st.launch_s) > 0
    assert parts == pytest.approx(st.dispatch_s, rel=0.01)
    assert parts <= st.dispatch_s
    if not recording:
        assert events == []
        return
    (disp,) = _by_name(events, "sim.pipeline.dispatch")
    assert disp["dur"] == st.dispatch_s
    for name, field in PARTS.items():
        spans = _by_name(events, name)
        assert spans and sum(e["dur"] for e in spans) == pytest.approx(
            getattr(st, field), rel=1e-12, abs=0)
        for e in spans:      # children of the dispatch span
            assert disp["ts"] <= e["ts"]
            assert e["ts"] + e["dur"] <= disp["ts"] + disp["dur"]
    assert len(_by_name(events, "sim.pipeline.sample")) == len(entries)
    assert len(_by_name(events, "sim.pipeline.launch")) == st.buckets


def test_every_span_of_a_call_carries_its_call():
    entries = _entries(algs=("hlp_ols",), n_sc=2)
    with obs.capture():
        for _ in range(2):
            pipelined_sweep_makespans(entries, noise=NOISE, seeds=[0, 1],
                                      workers=1, cache=False)
        events = obs.wall_events()
    calls = [e["args"]["call"] for e in _by_name(events,
                                                 "sim.pipeline.dispatch")]
    assert len(calls) == 2 and calls[1] == calls[0] + 1
    assert {e["args"].get("call") for e in events} == set(calls)
    assert not any(k.startswith("sim.pipeline") for k in obs.gauges())


# ----------------------------------------------------------- pool workers
@pytest.mark.parametrize("pool", ["thread", "process"])
def test_pooled_build_returns_worker_spans_on_the_parents_clock(
        pool, monkeypatch):
    """The registry no longer makes the build serial: the pool runs the
    solves and their spans come back inside the parent's build span."""
    monkeypatch.setenv("REPRO_PLAN_POOL", pool)
    used = []
    real = pipeline._process_pool if pool == "process" else \
        pipeline.ThreadPoolExecutor

    def spy(*a, **kw):
        used.append(pool)
        return real(*a, **kw)

    monkeypatch.setattr(pipeline, "_process_pool" if pool == "process"
                        else "ThreadPoolExecutor", spy)
    entries = _entries(algs=("hlp_ols", "heft"), n_sc=3)
    clear_plan_cache()
    with obs.capture():
        pipelined_sweep_makespans(entries, noise=NOISE, seeds=[0, 1],
                                  workers=4, cache=False)
        events = obs.wall_events()
    assert used and _obs.counter_value("plan_pool.broken") == 0
    (build,) = _by_name(events, "sim.pipeline.build")
    call = build["args"]["call"]
    allocs = _by_name(events, "plan.allocate")
    assert sorted(e["args"]["scheduler"] for e in allocs) == \
        sorted(s.name for _, _, s in entries)
    assert len(_by_name(events, "plan.order")) == 3   # hlp_ols only
    for e in allocs + _by_name(events, "plan.order") + \
            _by_name(events, "lp.solve"):
        assert e["args"]["call"] == call
        assert build["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= build["ts"] + build["dur"]


def test_worker_decision_records_match_a_serial_build():
    """Process (HiGHS) and thread (device LP) workers return decision
    records merged in submission order: equal to ``workers=1``'s."""
    entries = _entries(algs=("hlp_ols", "hlp_jax_ols", "heft"), n_sc=3)
    runs = {}
    for workers in (1, 4):
        with obs.capture():
            plans, _ = build_plans(entries, workers=workers, cache=False)
            runs[workers] = (plans, obs.decision_records())
    (p1, d1), (p4, d4) = runs[1], runs[4]
    assert d1 and d4 == d1
    assert [r.scheduler for r in d4] == [r.scheduler for r in d1]
    assert [plan_fingerprint(p) for p in p4] == \
        [plan_fingerprint(p) for p in p1]


def test_collect_keeps_a_workers_records_apart():
    with obs.capture():
        with _obs.tagged(call=7):
            with _obs.collect(worker=1) as rec:
                with obs.span("inner"):
                    pass
            assert obs.wall_events() == []
            _obs.merge(rec)
        (ev,) = obs.wall_events()
    assert ev["name"] == "inner" and ev["args"] == {"call": 7, "worker": 1}
    assert not obs.enabled()
    obs.reset()
    with _obs.collect() as rec:          # a process worker: enabled inside
        assert obs.enabled()
        with obs.span("w"):
            pass
    assert not obs.enabled() and len(rec.events) == 1
    _obs.merge(rec)                      # the parent is off: dropped
    assert obs.wall_events() == []


# ----------------------------------------------------------- golden hashes
def test_golden_schedule_hashes_with_the_registry_on_and_pooled():
    """``golden_width1.json``'s SHA-256 schedule hashes, from plans built
    through the pools while the registry records, replayed by the engine
    as they stand; the same plans with the registry off."""
    from repro.sim import simulate
    from repro.sim.adapters import FrozenPlanScheduler
    from tests.test_sim_golden import GOLDEN_W1, _sched_hash, _w1_suite

    entries, want = [], []
    for sc in _w1_suite():
        for alg in ("hlp_est", "hlp_ols", "hlp_jax_ols", "heft"):
            if alg in GOLDEN_W1[sc.name]:
                entries.append((sc, make_scheduler(alg)))
                want.append(GOLDEN_W1[sc.name][alg]["hash_clean"])
    grid = [(sc.graph, sc.machine, s) for sc, s in entries]
    off, _ = build_plans(grid, workers=4, cache=False)
    with obs.capture():
        on, _ = build_plans(grid, workers=4, cache=False)
    assert [plan_fingerprint(p) for p in on] == \
        [plan_fingerprint(p) for p in off]
    for (sc, _), plan, h in zip(entries, on, want):
        r = simulate(sc.graph, sc.machine, FrozenPlanScheduler(plan),
                     seed=sc.seed)
        assert _sched_hash(r.schedule) == h, sc.name


def test_thread_workers_keep_their_records_apart_under_switching():
    """More thread workers than cores, switching often: every worker's
    buffer holds exactly its own spans and records, and nothing reaches
    the registry but what the parent merges."""
    import os
    import sys
    from concurrent.futures import ThreadPoolExecutor

    def task(k):
        with _obs.collect(task=k) as rec:
            for i in range(20):
                with obs.span("stress", i=i):
                    _obs.record_decision((k, i))
        return rec

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with obs.capture():
            with ThreadPoolExecutor(max_workers=4 * (os.cpu_count() or 1)) \
                    as pool:
                futs = [pool.submit(task, k) for k in range(64)]
                recs = [f.result(timeout=60) for f in futs]
            assert obs.wall_events() == [] and obs.decision_records() == []
            for rec in recs:
                _obs.merge(rec)
            events, decisions = obs.wall_events(), obs.decision_records()
    finally:
        sys.setswitchinterval(old)
    assert obs.enabled() is False
    assert [(e["args"]["task"], e["args"]["i"]) for e in events] == \
        decisions == [(k, i) for k in range(64) for i in range(20)]
