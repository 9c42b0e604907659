"""Every public replay path runs the one bucket program and agrees with the
serial sweep bit-for-bit.

The paths differ only in how they group and pad plans (a bucket of one,
per-key buckets, full power-of-two envelopes, one caller-fixed envelope,
the pipelined executor's ``search_envelope`` buckets).  Phantom tasks
finish at 0 and never move a real lane, so each must return exactly the
makespans of the serial ``sweep_suite_makespans`` reference.
"""
import numpy as np
import pytest

from repro.sim import make_scheduler
from repro.sim.batch import (_bucket_key, batch_makespans, bucketed_makespans,
                             fixed_envelope_makespans, rollout_floors,
                             sample_actual_batch, search_envelope,
                             sweep_suite_makespans)
from repro.sim.engine import NoiseModel
from repro.sim.pipeline import pipelined_sweep_makespans
from repro.sim.scenarios import default_suite, moldable_suite

NOISE = NoiseModel("lognormal", 0.1)
SEEDS = [0, 1, 2, 3, 4]
SCENARIOS = {
    "rigid": (lambda: default_suite(seed=0)[3],
              ("heft", "hlp_ols", "hlp_est")),
    "moldable": (lambda: moldable_suite(seed=1, num=1)[0],
                 ("mhlp_ols", "heft", "hlp_ols")),
}


def _floors(g, plan):
    """Start floors of a machine whose k-th unit of each type is busy for
    another k mean task times."""
    busy = [g.proc.mean() * np.arange(8) for _ in range(2)]
    return rollout_floors(g, plan, busy)


def _inputs(entries):
    items = [(g, s.allocate(g, m)) for g, m, s in entries]
    times = [sample_actual_batch(g, p, NOISE, SEEDS) for g, p in items]
    return items, times, [_floors(g, p) for g, p in items]


def _batch(entries):
    items, times, _ = _inputs(entries)
    return [batch_makespans(g, p, t) for (g, p), t in zip(items, times)], False


def _bucketed(entries):
    items, times, floors = _inputs(entries)
    return bucketed_makespans(items, times, floors=floors), True


def _bucketed_envelope(entries):
    items, times, floors = _inputs(entries)
    return bucketed_makespans(items, times, floors=floors, envelope=True), True


def _fixed_envelope(entries):
    items, times, floors = _inputs(entries)
    g, machine, _ = entries[0]
    return fixed_envelope_makespans(items, times, search_envelope(g, machine),
                                    floors=floors), True


def _pipelined(entries):
    return pipelined_sweep_makespans(entries, noise=NOISE, seeds=SEEDS,
                                     floor_fn=_floors, workers=1), True


def _suite_pooled(entries):
    return sweep_suite_makespans(entries, noise=NOISE, seeds=SEEDS,
                                 floor_fn=_floors, workers=2,
                                 cache=True), True


PATHS = {
    "batch_makespans": _batch,
    "bucketed_makespans": _bucketed,
    "bucketed_makespans_envelope": _bucketed_envelope,
    "fixed_envelope_makespans": _fixed_envelope,
    "pipelined_sweep_makespans": _pipelined,
    "sweep_suite_makespans_pooled": _suite_pooled,
}


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
@pytest.mark.parametrize("path", sorted(PATHS))
def test_replay_path_matches_serial_sweep(path, scenario):
    make_sc, names = SCENARIOS[scenario]
    sc = make_sc()
    entries = [(sc.graph, sc.machine, make_scheduler(n)) for n in names]
    got, floored = PATHS[path](entries)
    want = sweep_suite_makespans(entries, noise=NOISE, seeds=SEEDS,
                                 floor_fn=_floors if floored else None)
    assert len(got) == len(want) == len(entries)
    for a, b in zip(got, want):
        assert a.shape == (len(SEEDS),)
        assert np.array_equal(a, b), (path, scenario, a, b)
    if floored:   # the floors reach the replay: they delay some plan
        free = sweep_suite_makespans(entries, noise=NOISE, seeds=SEEDS)
        assert any((a > b).any() for a, b in zip(want, free))


@pytest.mark.parametrize("suite", ["rigid", "moldable"])
def test_bucket_key_and_search_envelope_share_one_formula(suite):
    """A plan's own bucket key is the search envelope of its (g, machine)
    on rigid graphs, and fits inside it on moldable ones."""
    if suite == "rigid":
        scenarios, names = default_suite(seed=0), ("heft", "hlp_ols")
    else:
        scenarios, names = moldable_suite(seed=0), ("mhlp_ols", "heft")
    for sc in scenarios:
        env = search_envelope(sc.graph, sc.machine)
        for name in names:
            key = _bucket_key(sc.graph,
                              make_scheduler(name).allocate(sc.graph,
                                                            sc.machine))
            if suite == "rigid":
                assert key == env, (sc.name, name, key, env)
            else:
                assert key[0] <= env[0] and key[1] <= env[1], \
                    (sc.name, name, key, env)
