"""The plain plan-build references agree with the program where it is
sound, and tell a poorer plan from a sound one.

``reference.py`` imports nothing of the program; here both run on the
same generated Chameleon graphs, on the CPU.
"""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, ROOT)

from benchmarks.chip import reference as ref  # noqa: E402
from benchmarks.chip.players import graph_inputs, task_graph  # noqa: E402

CASES = [("potrf", 6, (4, 2), 1), ("getrf", 6, (8, 2), 2),
         ("getrf", 5, (16, 4), 3), ("potrf", 7, (32, 8), 4)]


def _case(app, nb, seed):
    inp = graph_inputs(app, nb, 512, 0.0, seed)
    return inp, task_graph(inp)


def _clean_makespan(inp, plan):
    _, edges, proc, comm = inp
    times = ref.realized_times(proc, plan.alloc, [None], 0.0)
    return ref.replay(len(proc), edges, comm, plan.alloc,
                      list(plan.sequences.values()), times)[0]


@pytest.mark.parametrize("app,nb,plat,seed", CASES)
def test_lp_optimum_matches_highs(app, nb, plat, seed):
    from repro.core.hlp import solve_hlp

    inp, g = _case(app, nb, seed)
    _, edges, proc, _ = inp
    sol = solve_hlp(g, *plat)
    optimum = ref.hlp_lambda(edges, proc, plat)
    assert optimum == pytest.approx(sol.lp_value, rel=1e-9)
    assert abs(ref.lp_gap(edges, proc, plat, sol.alloc, optimum)) < 1e-9


@pytest.mark.parametrize("app,nb,plat,seed", CASES)
def test_lp_gap_of_a_one_sided_allocation(app, nb, plat, seed):
    """Every task on the CPUs is no rounding of an optimal LP solution."""
    inp, _ = _case(app, nb, seed)
    _, edges, proc, _ = inp
    gap = ref.lp_gap(edges, proc, plat, np.zeros(len(proc), dtype=np.int64))
    assert gap > 0.05


@pytest.mark.parametrize("app,nb,plat,seed", CASES)
def test_ols_matches_the_program(app, nb, plat, seed):
    from repro.sim import Machine, make_scheduler

    inp, g = _case(app, nb, seed)
    _, edges, proc, _ = inp
    plan = make_scheduler("hlp_ols").allocate(g, Machine(plat))
    assert _clean_makespan(inp, plan) == ref.ols_makespan(edges, proc, plat,
                                                          plan.alloc)


@pytest.mark.parametrize("app,nb,plat,seed", CASES)
def test_heft_matches_the_program(app, nb, plat, seed):
    from repro.sim import Machine, make_scheduler

    inp, g = _case(app, nb, seed)
    _, edges, proc, _ = inp
    plan = make_scheduler("heft").allocate(g, Machine(plat))
    assert _clean_makespan(inp, plan) == ref.heft_makespan(edges, proc, plat)


def test_heft_without_insertion_is_another_schedule():
    inp, _ = _case("potrf", 6, 1)
    _, edges, proc, _ = inp
    assert ref.heft_makespan(edges, proc, (4, 2), insertion=False) \
        > ref.heft_makespan(edges, proc, (4, 2))
