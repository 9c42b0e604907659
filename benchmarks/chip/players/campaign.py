"""An allocation campaign on fresh scenarios: every plan misses the cache.

Each window step draws a new scenario — the next (application, platform)
of a seed-ordered cycle, with a fresh graph seed — builds one plan per
allocator through ``pipelined_sweep_makespans`` (LP solves in the process
pool, the device LP and HEFT in threads) and evaluates a clean row plus
``rows - 1`` noise rows of each.  Set-up warms the process pool, both
applications' bucket programs and the device LP for every platform of the
run's cycle.
"""
from __future__ import annotations

import numpy as np

from .. import reference as ref
from . import control_dtype, derive, graph_inputs, sweep, task_graph


class CampaignCell:
    module_patterns = ("_bucket_makespans", "_solve")

    def __init__(self, config, traffic, seed, chips):
        unknown = set(traffic["allocators"]) - set(PLAN_BUILD)
        if unknown:
            raise ValueError(f"no reference for allocators {sorted(unknown)}")
        if config["ccr"]:
            raise ValueError("the plan-build references are transfer-free")
        self.config, self.traffic, self.seed = config, traffic, seed
        self.rows = int(traffic["rows"])
        self.scale = float(traffic["noise"]["scale"])
        self.plan_build_s: list[float] = []
        self.steps: list[dict] = []
        self.attempted = self.failed = 0
        self.templates: dict = {}
        self.cycle = self._cycle(config, traffic, seed)

    @staticmethod
    def _cycle(config, traffic, seed) -> list[tuple[str, tuple]]:
        """The (application, platform) of step k is ``cycle[k % len]``.

        A run uses one platform of each CPU count with the GPU counts in a
        seed-drawn order (a Latin square over the configuration's grid), so
        every seed plans on the same total of processors, and over seeds
        every platform comes up.  Applications alternate."""
        plats = [tuple(p) for p in config["platforms"]]
        ms = sorted({p[0] for p in plats})
        ks = sorted({p[1] for p in plats})
        rng = np.random.default_rng([seed, 0x9A7])
        kperm = rng.permutation(len(ks))
        chosen = [(m, ks[kperm[i % len(ks)]]) for i, m in enumerate(ms)]
        chosen = [chosen[i] for i in rng.permutation(len(chosen))]
        apps = config["apps"]
        return [(apps[k % len(apps)], chosen[(k // len(apps)) % len(chosen)])
                for k in range(len(chosen) * len(apps))]

    def _scenario(self, app, platform, gseed):
        from repro.sim import Machine, make_scheduler

        c = self.config
        inp = graph_inputs(app, c["nb_blocks"], c["block_size"], c["ccr"],
                           gseed)
        g = task_graph(inp, self.templates)
        machine = Machine(tuple(platform))
        return inp, [(g, machine, make_scheduler(a))
                     for a in self.traffic["allocators"]]

    # ----------------------------------------------------------- set-up
    def setup(self):
        from repro.sim import Machine, make_scheduler

        seeds = np.arange(1, self.rows, dtype=np.int64)
        plats = {p for _, p in self.cycle}
        for a, app in enumerate(self.config["apps"]):
            first = self.cycle[a][1]
            _, entries = self._scenario(app, first,
                                        derive(self.seed, 0x3A4, a))
            sweep(entries, seeds, self.scale)
            g = entries[0][0]
            lp = make_scheduler("hlp_jax_ols")
            for p in sorted(plats - {first}):
                lp.allocate(g, Machine(p))

    # ----------------------------------------------------------- window
    def run_window(self, window):
        # the window closes only after a whole round of the applications:
        # their steps differ in length (getrf's nearly twice potrf's)
        k, apps = 0, len(self.config["apps"])
        while not window.tick(boundary=k % apps == 0):
            app, platform = self.cycle[k % len(self.cycle)]
            inp, entries = self._scenario(app, platform,
                                          derive(self.seed, 0xF5E, k))
            seeds = np.random.default_rng([self.seed, 0x5EED, k]).integers(
                0, 2 ** 62, size=self.rows - 1)
            with window.span("bench.step"):
                out, stats = sweep(entries, seeds, self.scale, window.spans)
            self.plan_build_s.append(stats.plan_build_s)
            self.steps.append({"inputs": inp, "entries": entries,
                               "platform": platform, "seeds": seeds,
                               "out": out})
            self.attempted += len(entries)
            k += 1

    def release(self):
        pass

    # ------------------------------------------------------------ check
    def checks(self, rng, control: bool = False) -> dict:
        """Every plan of the window, against the plain reference:

        * ``plan_faults``: faulty plans;
        * ``makespan_rel_err``: the worst relative gap of its makespans to
          the reference replay of the plan;
        * ``lp_gap.<allocator>``: for an LP allocator, how far the best
          LP solution that rounds to its allocation lies above the
          reference LP optimum (relative);
        * ``ols_gap``: for an LP allocator, the relative gap of the clean
          makespan to the reference list scheduling of its allocation;
        * ``heft_gap``: for HEFT, the relative gap of the clean makespan
          to the reference HEFT's."""
        from repro.sim.pipeline import cached_allocate

        out = {"makespan_rel_err": 0.0, "plan_faults": 0}
        for st in self.steps:
            _, edges, proc, comm = st["inputs"]
            plat, n = st["platform"], len(proc)
            seeds = [None] + [int(s) for s in st["seeds"]]
            optimum = None
            for (g, machine, sched), got in zip(st["entries"], st["out"]):
                p = cached_allocate(sched, g, machine)
                out["plan_faults"] += ref.plan_faults(n, edges, plat, p.alloc,
                                                      p.sequences)
                seqs = list(p.sequences.values())
                times = ref.realized_times(proc, p.alloc, seeds, self.scale)
                want = ref.replay(n, edges, comm, p.alloc, seqs, times)
                if control:
                    got = ref.replay(n, edges, comm, p.alloc, seqs, times,
                                     dtype=control_dtype())
                _worst(out, "makespan_rel_err", ref.rel_err(got, want))
                clean = want[0]
                kind = PLAN_BUILD[sched.name]
                if kind == "lp":
                    if optimum is None:
                        optimum = ref.hlp_lambda(edges, proc, plat)
                    _worst(out, f"lp_gap.{sched.name}",
                           ref.lp_gap(edges, proc, plat, p.alloc, optimum))
                    best = ref.ols_makespan(edges, proc, plat, p.alloc)
                    _worst(out, "ols_gap", abs(clean - best) / best)
                else:
                    best = ref.heft_makespan(edges, proc, plat)
                    _worst(out, "heft_gap", abs(clean - best) / best)
        return out


# how the reference rebuilds each allocator's plan: an LP allocation then
# list scheduling by rank, or HEFT
PLAN_BUILD = {"hlp_ols": "lp", "hlp_jax_ols": "lp", "heft": "heft"}


def _worst(out: dict, key: str, value: float) -> None:
    out[key] = max(out.get(key, -np.inf), float(value))


def make(config, traffic, seed, chips):
    return CampaignCell(config, traffic, seed, chips)
