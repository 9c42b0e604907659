"""The work count behind ``replay_roofline`` (``benchmarks/chip/work.py``).

It comes from the unpadded plans, so one set of plans counts the same
whether the replay runs them bucketed, padded to an envelope or one at a
time: the count equals what the real (unmasked) slots of each of those
layouts hold.
"""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

from benchmarks.chip.players import graph_inputs, task_graph  # noqa: E402
from benchmarks.chip.work import Work, replay_work, roofline_s  # noqa: E402


@pytest.fixture(scope="module")
def plans():
    from repro.sim import Machine, make_scheduler

    machine = Machine((6, 2))
    out = []
    for app, nb in (("potrf", 4), ("getrf", 4), ("potrf", 6)):
        inp = graph_inputs(app, nb, 320, 0.5, seed=nb)
        g = task_graph(inp)
        for name in ("heft", "er_ls"):
            from repro.sim import plan_for
            out.append((g, plan_for(name, g, machine)))
    return out


def _count(items):
    return [(g.n, g.num_edges, list(p.sequences.values())) for g, p in items]


def _from_layout(bd, ns, rows):
    """Operations implied by a padded bucket's real slots."""
    edges = int(np.asarray(bd.pred_mask).sum())
    return rows * (2 * edges + sum(ns))


@pytest.mark.parametrize("layout", ["bucketed", "envelope", "single"])
def test_count_is_the_same_in_every_layout(plans, layout):
    from repro.sim.batch import BatchedPlanDag, bucket_plans, search_envelope

    rows = 7
    want = replay_work(_count(plans), rows)
    if layout == "single":
        groups = [[i] for i in range(len(plans))]
        pads = [None] * len(groups)
    elif layout == "bucketed":
        groups = list(bucket_plans(plans).values())
        pads = [None] * len(groups)
    else:
        groups = [list(range(len(plans)))]
        g = max((p[0] for p in plans), key=lambda g: g.n)
        pads = [tuple(2 * x for x in search_envelope(g, (6, 2)))]
    ops = 0
    for idxs, pad in zip(groups, pads):
        items = [plans[i] for i in idxs]
        bd = BatchedPlanDag.from_plans(items, pad_to=pad)
        ops += _from_layout(bd, [g.n for g, _ in items], rows)
        part = replay_work(_count(items), rows)
        assert part.ops == _from_layout(bd, [g.n for g, _ in items], rows)
    assert ops == want.ops


def test_count_adds_over_plans(plans):
    whole = replay_work(_count(plans), 3)
    parts = [replay_work(_count([p]), 3) for p in plans]
    assert whole.ops == sum(p.ops for p in parts)
    assert whole.bytes == sum(p.bytes for p in parts)


def test_roofline_names_its_bound():
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = roofline_s(Work(ops=1.75e9, bytes=1e9), peaks, 1)
    assert bound == "bytes" and t == pytest.approx(1e9 / 819e9)
    t4, _ = roofline_s(Work(ops=1.75e9, bytes=1e9), peaks, 4)
    assert t4 == pytest.approx(t / 4)
    t, bound = roofline_s(Work(ops=1e15, bytes=1.0), peaks, 1)
    assert bound == "ops" and t == pytest.approx(1e15 / 197e12)
