#!/usr/bin/env python3
"""Read the program's and the control's numbers of a cell, seed by seed.

    python3 benchmarks/chip/control.py --workload <name> --seeds 1,2,3 \
        [--seconds 5] [--fault <name>]

For each seed, in this one process: the cell's set-up, a short window at
the cell's own load, then every compared number twice: as the program's
answers give it, and with the control in the program's place — the plain
reference computed in bfloat16, the precision below the float32 the
evaluator computes in.  The control has to fail a limit the program
meets.  With ``--fault`` the program's plan build is broken underneath
for the whole run (see ``FAULTS``), and its numbers are the fault's
readings.  One JSON line per seed.  The benchmark's own runs never run
this.
"""
import argparse
import contextlib
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]
# JAX's persistent compilation cache lives in the checkout, at a fixed
# path, whatever the environment names: JAX reads this when imported.
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(_ROOT, ".jax_cache")


def _jax_lp_truncated():
    """The device LP stopped after a tenth of its iterations."""
    from repro.sim import adapters

    init = adapters.HLPJaxOLSScheduler.__init__
    return adapters.HLPJaxOLSScheduler, "__init__", \
        lambda self, iters=30, seed=0: init(self, iters, seed)


def _highs_swapped():
    """``hlp_ols`` solves its LP with the device LP instead of HiGHS."""
    from repro.core.hlp_jax import solve_hlp_jax
    from repro.sim import adapters

    return adapters, "solve_hlp", lambda g, m, k: solve_hlp_jax(g, m, k, 300)


def _ols_unranked():
    """The LP allocators' list scheduling ignores the rank (natural
    order, the paper's EST)."""
    from repro.core.listsched import hlp_est
    from repro.sim import adapters

    return adapters, "hlp_ols", \
        lambda g, machine, alloc, *a, **k: hlp_est(g, machine, alloc)


def _heft_uninserted():
    """HEFT keeps its allocation but list-schedules it without insertion
    into idle gaps."""
    from repro.core.listsched import hlp_est
    from repro.sim import adapters

    heft = adapters.heft
    return adapters, "heft", \
        lambda g, machine, **k: hlp_est(g, machine, heft(g, machine).alloc)


FAULTS = {"jax_lp_truncated": _jax_lp_truncated,
          "highs_swapped": _highs_swapped,
          "ols_unranked": _ols_unranked,
          "heft_uninserted": _heft_uninserted}


@contextlib.contextmanager
def planted(fault: str | None):
    """Plan build broken by ``fault`` inside the block (nothing for
    ``None``).  Plans are then built in threads, where the patch holds, and
    no plan cached on either side of the block is served on the other."""
    if fault is None:
        yield
        return
    from repro.sim.pipeline import clear_plan_cache

    obj, attr, broken = FAULTS[fault]()
    orig, pool = getattr(obj, attr), os.environ.get("REPRO_PLAN_POOL")
    setattr(obj, attr, broken)
    os.environ["REPRO_PLAN_POOL"] = "thread"
    clear_plan_cache()
    try:
        yield
    finally:
        clear_plan_cache()
        setattr(obj, attr, orig)
        if pool is None:
            os.environ.pop("REPRO_PLAN_POOL")
        else:
            os.environ["REPRO_PLAN_POOL"] = pool


def readings(workload: str, seed: int, seconds: float, *,
             fault: str | None = None, require_tpu: bool = True,
             rehearse: bool = False) -> dict:
    import numpy as np

    from benchmarks.chip import device, harness

    cell = harness.load_cell(workload, rehearse=rehearse)
    device.devices(cell.chips, require_tpu=require_tpu)
    obj = harness.load_player(cell.traffic["player"]).make(
        cell.config, cell.traffic, seed, cell.chips)
    with planted(fault):
        obj.setup()
        window = harness.Window(seconds)
        window.start()
        obj.run_window(window)
        obj.release()
        program = obj.checks(np.random.default_rng([seed, 0xC4EC]))
        control = obj.checks(np.random.default_rng([seed, 0xC4EC]),
                             control=True)
    return {"seed": seed, "fault": fault, "program": program,
            "control": control, "limits": cell.traffic["limits"]}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--fault", choices=sorted(FAULTS))
    args = ap.parse_args()

    from benchmarks.chip import harness
    from repro.sim import configure_xla_cache

    configure_xla_cache()
    try:
        for s in args.seeds.split(","):
            t0 = time.perf_counter()
            out = readings(args.workload, int(s), args.seconds,
                           fault=args.fault)
            out["seconds"] = time.perf_counter() - t0
            print(json.dumps(out), flush=True)
    finally:
        harness.stop_processes()
    return 0


if __name__ == "__main__":
    sys.exit(main())
