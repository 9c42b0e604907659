"""The program's spans as the chip benchmark reads them.

* ``spans.per_call_ms`` sums a part's spans per call and reads nothing from
  a program whose spans carry no ``call`` (the parent of this reader).
* A ``repro.obs`` span opens a profiler ``TraceAnnotation``: on a trace
  recorded here its start agrees with the span's ``perf_counter`` start
  put on the trace's clock through the benchmark's anchor.
* The traced replay cell, rehearsed on the CPU, reports the three parts of
  its dispatch loop, and they add up to ``dispatch_ms.replay``.
"""
import glob
import os
import sys
import time
from types import SimpleNamespace

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, ROOT)

from benchmarks.chip import harness  # noqa: E402
from benchmarks.chip import trace as T  # noqa: E402
from benchmarks.chip.spans import per_call_ms  # noqa: E402

PARTS = ("noise_sample_ms.replay", "plan_dag_ms.replay", "launch_ms.replay")


def _ev(name, dur, **args):
    return {"name": name, "cat": "wall", "ts": 0.0, "dur": dur, "args": args}


def test_per_call_sums_a_part_over_calls():
    run = SimpleNamespace(obs_events=[
        _ev("sim.pipeline.dispatch", 1.0, call=1),
        _ev("sim.pipeline.sample", 0.25, call=1, plan=0),
        _ev("sim.pipeline.sample", 0.5, call=1, plan=1),
        _ev("sim.pipeline.dispatch", 1.0, call=2),
        _ev("sim.pipeline.sample", 0.25, call=2, plan=0),
        _ev("sim.pipeline.sample", 9.0, call=3, plan=0),   # no dispatch
    ])
    assert per_call_ms(run, "sim.pipeline.sample") == pytest.approx(500.0)
    assert per_call_ms(run, "sim.pipeline.launch") is None


def test_a_program_without_the_spans_gives_nothing():
    """Per-bucket dispatch spans with no ``call`` tag, as an older program
    records them: every new reader returns ``None``."""
    run = SimpleNamespace(obs_events=[
        _ev("sim.pipeline.build", 0.1, plans=8),
        _ev("sim.pipeline.dispatch", 0.7, bucket="2048x4", plans=4),
        _ev("sim.pipeline.drain", 0.01, buckets=2)])
    for name in PARTS:
        assert harness.load_metric(name)(run) is None


def test_span_annotation_agrees_with_the_anchor():
    """Within 1 ms on a CPU profiler trace: the ``TraceAnnotation`` a
    registry span opens, and its ``perf_counter`` start mapped through
    the anchor."""
    import jax  # noqa: F401  (the span annotates only where jax is loaded)
    from jax.profiler import ProfileData

    from repro import obs

    tr = T.Tracer()
    tr.begin()
    with obs.capture():
        for _ in range(3):
            with obs.span("chipbench.test.span"):
                time.sleep(0.01)
            time.sleep(0.005)
        events = [e for e in obs.wall_events()
                  if e["name"] == "chipbench.test.span"]
    tr.end()
    (path,) = glob.glob(os.path.join(tr.dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    pd = ProfileData.from_file(path)
    starts = sorted(e.start_ns for plane in pd.planes
                    if plane.name.startswith("/host:")
                    for line in plane.lines for e in line.events
                    if e.name == "chipbench.test.span")
    tr.read()
    assert len(starts) == len(events) == 3
    for s, e in zip(starts, sorted(events, key=lambda e: e["ts"])):
        assert abs(tr.to_ns(e["ts"]) - s) < 1e6


@pytest.fixture
def _small_pool(monkeypatch):
    monkeypatch.setenv("REPRO_PLAN_WORKERS", "2")


def test_traced_replay_reports_the_dispatch_parts(_small_pool):
    out = harness.run("replay.chameleon20.mc1024", 2 ** 31 + 29, 0.3, True,
                      t_start=time.perf_counter(), require_tpu=False,
                      rehearse=True)
    m = out["metrics"]
    assert out["correct"] and set(PARTS) <= set(m)
    assert all(m[k]["value"] > 0 and m[k]["unit"] == "ms" for k in PARTS)
    total = sum(m[k]["value"] for k in PARTS)
    assert total == pytest.approx(m["dispatch_ms.replay"]["value"], rel=0.1)
    assert total <= m["dispatch_ms.replay"]["value"]
    gaps = dict(out["breakdown"]["idle_gaps"])
    assert "sim.pipeline.assemble" in gaps
