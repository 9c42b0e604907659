"""The trace reduction of the chip benchmark (``benchmarks/chip/trace.py``).

Interval arithmetic on synthetic intervals, the TPU trace layout on a
stand-in of its planes, and the whole path on a trace recorded here on the
CPU backend.
"""
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

from benchmarks.chip import trace as T  # noqa: E402


def test_union_clips_and_merges():
    got = T.union([(5, 8), (0, 3), (2, 4), (9, 20)], 1, 12)
    assert got == [(1, 4), (5, 8), (9, 12)]
    assert T.covered([(5, 8), (0, 3), (2, 4), (9, 20)], 1, 12) == 9


def test_gaps_are_the_complement_of_busy():
    busy = T.union([(2, 4), (6, 7)], 0, 10)
    assert T.gaps(busy, 0, 10) == [(0, 2), (4, 6), (7, 10)]


def test_gap_instants_go_to_the_innermost_span():
    spans = [("step", 0, 100), ("sample", 2, 5), ("dispatch", 25, 40)]
    got = T.charge_gaps([(0, 10), (20, 30), (200, 210)], spans)
    assert got == {"step": 12.0, "sample": 3.0, "dispatch": 5.0,
                   "none": 10.0}


def test_tpu_names_are_shortened():
    assert T.module_name("jit__bucket_makespans(3758328602887399164)") == \
        "jit__bucket_makespans"
    text = ("%while.98 = (s32[]{:T(128)}, f32[220]{0:T(256)S(1)}) "
            "while((s32[]{:T(128)}) %tuple.18), condition=%c, body=%b")
    assert T.op_name(text) == "while.98"


class _Ev:
    def __init__(self, name, start, dur):
        self.name, self.start_ns, self.duration_ns = name, start, dur
        self.stats = {}


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class _Profile:
    def __init__(self, planes):
        self.planes = planes


def test_tpu_layout_keeps_outermost_ops_and_reduces():
    """The TPU plane's layout: a loop's per-iteration ops nest inside the
    loop's own event; modules on their own line; the anchor on the host."""
    dev = _Plane("/device:TPU:0", [
        _Line("XLA Modules", [_Ev("jit__bucket_makespans(7)", 100, 50),
                              _Ev("jit__solve(9)", 300, 100)]),
        _Line("XLA Ops", [_Ev("%while.1 = (f32[]) while(...)", 100, 50),
                          _Ev("%add.2 = f32[] add(...)", 110, 5),
                          _Ev("%add.2 = f32[] add(...)", 120, 5),
                          _Ev("%fusion.3 = f32[] fusion(...)", 300, 100)]),
    ])
    host = _Plane("/host:CPU", [_Line("python3", [
        _Ev(T.ANCHOR, 90, 1), _Ev("PjitFunction(f)", 95, 2)])])
    tr = T.read_profile(_Profile([dev, host, _Plane("/host:metadata", [])]))
    assert [o[0] for o in tr.ops[0]] == ["while.1", "fusion.3"]
    red = T.reduce(tr, 90, 490, 1, ["_bucket_makespans", "_solve"],
                   [("host.work", 150, 300)])
    assert red.busy_s == pytest.approx(150e-9)
    assert red.window_s == pytest.approx(400e-9)
    assert red.idle_pct == pytest.approx(100 * (1 - 150 / 400))
    assert red.module_s == {"_bucket_makespans": pytest.approx(50e-9),
                            "_solve": pytest.approx(100e-9)}
    assert red.device_ops[0] == ["jit__solve/fusion.3", pytest.approx(1e-7)]
    assert dict((k, v) for k, v in red.idle_gaps) == {
        "host.work": pytest.approx(150e-9), "none": pytest.approx(100e-9)}


def test_recorded_cpu_trace_reduces():
    """A trace recorded here: two calls of a jitted ``_bucket_makespans``
    with host sleeps between them, inside a benchmark span."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def _bucket_makespans(x):
        return jnp.sin(x) @ x

    x = jnp.ones((256, 256))
    _bucket_makespans(x).block_until_ready()
    tr = T.Tracer()
    tr.begin()
    spans = []
    for _ in range(2):
        t0 = time.perf_counter()
        time.sleep(0.03)
        spans.append(("host.sleep", t0, time.perf_counter() - t0))
        _bucket_makespans(x).block_until_ready()
    tr.end()
    tr.read()
    lo, hi = tr.window_ns()
    red = T.reduce(tr.trace, lo, hi, 1, ["_bucket_makespans"],
                   tr.host_spans(spans))
    ops = [(s, e) for _, s, e in tr.trace.ops[0]]
    assert red.busy_s == pytest.approx(T.covered(ops, lo, hi) / 1e9)
    assert 0 < red.busy_s < red.window_s
    assert red.idle_pct == pytest.approx(
        100 * (1 - red.busy_s / red.window_s))
    assert 0 < red.module_s["_bucket_makespans"] <= red.busy_s + 1e-12
    assert all(k.startswith("jit__bucket_makespans/")
               for k, _ in red.device_ops)
    idle = dict((k, v) for k, v in red.idle_gaps)
    assert idle["host.sleep"] >= 0.05       # two 30 ms sleeps, device idle
    assert sum(idle.values()) == pytest.approx(red.window_s - red.busy_s)
