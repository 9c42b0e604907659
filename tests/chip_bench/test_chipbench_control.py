"""The comparison that decides ``correct`` fails when it should.

The control — the plain reference in bfloat16 put in the program's place —
has to read above a limit the program meets.  And a run whose timed path
is broken underneath (an answer altered where it is made, half of a batch
left out, a step that hands back its first answers again, a plan built
wrongly) has to end with ``correct`` false.  No cell spans chips yet, so
none can leave out an exchange between them.
Both at the files' rehearsal sizes, on the CPU.
"""
import os
import sys
import time

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmarks", "chip")]

import control  # noqa: E402

from benchmarks.chip import harness  # noqa: E402

REPLAY, CAMPAIGN = "replay.chameleon20.mc1024", "campaign.chameleon10.fresh"
COMPARED = {REPLAY: "makespan_rel_err", CAMPAIGN: "makespan_rel_err"}


@pytest.fixture(autouse=True)
def _small_pool(monkeypatch):
    monkeypatch.setenv("REPRO_PLAN_WORKERS", "2")
    yield
    harness._stop_pool()


@pytest.mark.parametrize("cell", sorted(COMPARED))
def test_control_fails_where_the_program_passes(cell):
    key = COMPARED[cell]
    r = control.readings(cell, 2 ** 31 + 3, 0.3, require_tpu=False,
                         rehearse=True)
    assert r["program"][key] <= r["limits"][key] < r["control"][key]


# ---------------------------------------------------------------- faults
def _altered(out):
    return out.at[0, 0].multiply(1.01)


def _half_left_out(out):
    """The second half of the batch copies the first."""
    B, S = out.shape
    if S > 1:
        return out.at[:, S - S // 2:].set(out[:, :S // 2])
    return out.at[B - B // 2:].set(out[:B // 2])


def _patch_evaluator(monkeypatch, fault):
    import repro.sim.batch as batch
    import repro.sim.pipeline as pipeline

    orig = batch._bucket_makespans_sharded

    def broken(*args, **kw):
        return fault(orig(*args, **kw))

    monkeypatch.setattr(batch, "_bucket_makespans_sharded", broken)
    monkeypatch.setattr(pipeline, "_bucket_makespans_sharded", broken)


def _stale_answers(monkeypatch):
    """Each campaign call hands back the first call's makespans."""
    import repro.sim.pipeline as pipeline

    orig, first = pipeline.pipelined_sweep_makespans, []

    def stale(entries, **kw):
        out = orig(entries, **kw)
        if not first:
            first.append(out)
        return first[0] if len(first[0]) == len(out) else out

    monkeypatch.setattr(pipeline, "pipelined_sweep_makespans", stale)


FAULTS = {
    REPLAY: ["altered", "half", "stale"],
    CAMPAIGN: ["altered", "half", *sorted(control.FAULTS)],
}


@pytest.mark.parametrize("cell,fault", [(c, f) for c in FAULTS
                                        for f in FAULTS[c]])
def test_a_broken_timed_path_is_not_correct(monkeypatch, cell, fault):
    if fault == "stale":
        _stale_answers(monkeypatch)
    elif fault in ("altered", "half"):
        _patch_evaluator(monkeypatch, {"altered": _altered,
                                       "half": _half_left_out}[fault])
    with control.planted(fault if fault in control.FAULTS else None):
        out = harness.run(cell, 2 ** 31 + 29, 0.3, False,
                          t_start=time.perf_counter(), require_tpu=False,
                          rehearse=True)
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())
