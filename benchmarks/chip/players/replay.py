"""Monte Carlo replay of fixed plans: the noise study of a campaign.

Set-up builds one plan per (application × block size × allocator) of the
configuration and warms the bucket programs.  Each window step is one
``pipelined_sweep_makespans`` call over all the plans: a clean row plus
``rows - 1`` lognormal rows from fresh seeds, so every step asks for
``plans × rows`` new makespan evaluations.  The plans come from the plan
cache, so the step is noise sampling, plan-DAG assembly and the bucket
program.
"""
from __future__ import annotations

import time

import numpy as np

from .. import reference as ref
from ..work import replay_work
from . import control_dtype, derive, graph_inputs, sweep, task_graph


class ReplayCell:
    module_patterns = ("_bucket_makespans",)

    def __init__(self, config, traffic, seed, chips):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.rows = int(traffic["rows"])
        self.scale = float(traffic["noise"]["scale"])
        self.step_times: list[tuple[float, float]] = []
        self.dispatch_s: list[float] = []
        self.results: list[list[np.ndarray]] = []
        self.row_seeds: list[np.ndarray] = []
        self.attempted = self.failed = 0

    # ----------------------------------------------------------- set-up
    def setup(self):
        from repro.sim import Machine, make_scheduler

        c = self.config
        machine = Machine(tuple(c["platform"]))
        self.inputs, self.entries, templates = [], [], {}
        for app in c["apps"]:
            for block in c["block_sizes"]:
                inp = graph_inputs(app, c["nb_blocks"], block, c["ccr"],
                                   derive(self.seed, 0x6EA, block,
                                          c["apps"].index(app)))
                g = task_graph(inp, templates)
                for name in self.traffic["allocators"]:
                    self.inputs.append(inp)
                    self.entries.append((g, machine, make_scheduler(name)))
        # the warm-up builds the plans (the plan cache keeps them) and
        # compiles every bucket at the window's own row count
        sweep(self.entries, np.arange(1, self.rows, dtype=np.int64),
              self.scale)
        self.plans = [self._plan(i) for i in range(len(self.entries))]
        self.work = replay_work(
            [(len(inp[2]), len(inp[1]), list(p.sequences.values()))
             for inp, p in zip(self.inputs, self.plans)], self.rows)

    def _plan(self, i):
        from repro.sim.pipeline import cached_allocate

        g, machine, sched = self.entries[i]
        return cached_allocate(sched, g, machine)

    # ----------------------------------------------------------- window
    def run_window(self, window):
        k = 0
        while not window.tick():
            seeds = np.random.default_rng([self.seed, 0x5EED, k]).integers(
                0, 2 ** 62, size=self.rows - 1)
            t0 = time.perf_counter()
            with window.span("bench.step"):
                out, stats = sweep(self.entries, seeds, self.scale,
                                   window.spans)
            self.step_times.append((t0, time.perf_counter()))
            self.dispatch_s.append(stats.dispatch_s)
            self.results.append(out)
            self.row_seeds.append(seeds)
            self.attempted += sum(o.size for o in out)
            k += 1

    def release(self):
        pass

    # ------------------------------------------------------------ check
    def checks(self, rng, control: bool = False) -> dict:
        """The worst relative gap of a sample of the window's makespans to
        the reference replay, and the number of faulty plans."""
        steps = rng.choice(len(self.results),
                           size=min(int(self.traffic["check_steps"]),
                                    len(self.results)), replace=False)
        counts = self.config["platform"]
        faults = sum(ref.plan_faults(len(inp[2]), inp[1], counts, p.alloc,
                                     p.sequences)
                     for inp, p in zip(self.inputs, self.plans))
        worst = 0.0
        for k in sorted(int(s) for s in steps):
            seeds = [None] + [int(s) for s in self.row_seeds[k]]
            for i, (inp, p) in enumerate(zip(self.inputs, self.plans)):
                _, edges, proc, comm = inp
                seqs = list(p.sequences.values())
                times = ref.realized_times(proc, p.alloc, seeds, self.scale)
                want = ref.replay(len(proc), edges, comm, p.alloc, seqs, times)
                got = (ref.replay(len(proc), edges, comm, p.alloc, seqs,
                                  times, dtype=control_dtype())
                       if control else self.results[k][i])
                worst = max(worst, ref.rel_err(got, want))
        return {"makespan_rel_err": worst, "plan_faults": faults}


def make(config, traffic, seed, chips):
    return ReplayCell(config, traffic, seed, chips)
