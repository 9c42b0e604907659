#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 benchmarks/chip/run_cell.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cell (``workloads`` in ``BENCHMARK.json``) names its configuration,
traffic mix and metrics.  Set-up builds the inputs from ``--seed`` and
warms every shape the window uses; the window then runs for ``--seconds``.
With ``--trace 1`` a profiler trace over a few seconds of the window gives
the per-layer metrics, ``busy_s`` and ``breakdown``; with ``--trace 0``
the line carries the end-to-end metrics.  Afterwards a sample of the
window's answers is checked against the plain reference
(``reference.py``): each compared number goes to standard error with its
limit, and under ``checks`` at the end of the line.

Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]
# JAX's persistent compilation cache lives in the checkout, at a fixed
# path, whatever the environment names: JAX reads this when imported.
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(_ROOT, ".jax_cache")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmarks.chip import harness
    from benchmarks.chip.device import NoChip

    try:
        out = harness.run(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=T_START)
    except NoChip as e:
        print(f"run_cell: {e}", file=sys.stderr)
        return 2
    finally:
        harness.stop_processes()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
