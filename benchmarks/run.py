"""Benchmark harness — one entry per paper table/figure (+ roofline report).

Usage:  PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME[,NAME]]

Prints ``name,us_per_call,derived`` CSV lines (one per algorithm/campaign)
followed by a summary that checks the paper's §6 experimental claims.
Detailed per-instance CSVs land in artifacts/, and every run writes a
``BENCH_sim.json`` perf trajectory (schema ``repro.bench.v1``: wall-clock
per sub-campaign, XLA compile counts, plans-evaluated/sec per device, mesh
shape, seed) — diff two of them across PRs with
``python -m benchmarks.render_tables --diff-bench OLD NEW``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

from repro import obs

#: structured per-bench extras for the BENCH_sim.json trajectory — bench
#: functions stash metrics here (keyed by bench name) as they run, and
#: ``main`` merges them with its own wall-clock/line accounting.
BENCH_EXTRAS: dict[str, dict] = {}


def bench_offline2(full: bool, seed: int = 0) -> list[str]:
    from . import campaign
    with obs.timer("bench.offline2") as sp:
        r = campaign.offline_2type(full=full)
    dt = sp.dur
    lines = []
    per = dt / max(r["runs"], 1) * 1e6
    for alg in ("hlp_est", "hlp_ols", "heft"):
        lines.append(f"offline2/{alg},{per:.0f},mean_ratio_lp={r['ratios'][alg]:.4f}")
    ols_est = (r["ratios"]["ols_vs_est"] - 1) * 100
    ols_heft = (r["ratios"]["ols_vs_heft"] - 1) * 100
    lines.append(f"offline2/ols_vs_est,{per:.0f},improvement_pct={ols_est:.2f}")
    lines.append(f"offline2/ols_vs_heft,{per:.0f},improvement_pct={ols_heft:.2f}")
    print(f"# offline-2type: {r['runs']} runs in {dt:.1f}s | "
          f"mean ratios EST={r['ratios']['hlp_est']:.3f} "
          f"OLS={r['ratios']['hlp_ols']:.3f} HEFT={r['ratios']['heft']:.3f} | "
          f"max OLS ratio={r['max_ratio']['hlp_ols']:.3f}")
    print(f"#   paper claims: OLS improves EST ~8-10% -> measured {ols_est:+.1f}%;"
          f" OLS vs HEFT ~+2% -> measured {ols_heft:+.1f}%;"
          f" ratios <= 2 -> max {max(r['max_ratio'].values()):.2f}")
    return lines


def bench_offline3(full: bool, seed: int = 0) -> list[str]:
    from . import campaign
    with obs.timer("bench.offline3") as sp:
        r = campaign.offline_3type(full=full)
    dt = sp.dur
    per = dt / max(r["runs"], 1) * 1e6
    lines = [f"offline3/{alg},{per:.0f},mean_ratio_lp={r['ratios'][alg]:.4f}"
             for alg in ("qhlp_est", "qhlp_ols", "qheft")]
    est_ols = (r["ratios"]["ols_vs_est"] - 1) * 100
    heft_ols = (r["ratios"]["heft_vs_ols"] - 1) * 100
    lines.append(f"offline3/ols_vs_est,{per:.0f},improvement_pct={est_ols:.2f}")
    lines.append(f"offline3/qheft_vs_ols,{per:.0f},qheft_advantage_pct={heft_ols:.2f}")
    print(f"# offline-3type: {r['runs']} runs in {dt:.1f}s | mean ratios "
          f"QEST={r['ratios']['qhlp_est']:.3f} QOLS={r['ratios']['qhlp_ols']:.3f} "
          f"QHEFT={r['ratios']['qheft']:.3f}")
    print(f"#   paper claims: QHEFT ~5% better than QHLP-OLS -> measured "
          f"{heft_ols:+.1f}% ; ratios <= 2 -> max {max(r['max_ratio'].values()):.2f}")
    return lines


def bench_online(full: bool, seed: int = 0) -> list[str]:
    from . import campaign
    with obs.timer("bench.online") as sp:
        r = campaign.online_2type(full=full)
    dt = sp.dur
    per = dt / max(r["runs"], 1) * 1e6
    lines = [f"online/{alg},{per:.0f},mean_ratio_lp={r['ratios'][alg]:.4f}"
             for alg in ("er_ls", "eft", "greedy", "random")]
    vs_greedy = (r["ratios"]["erls_vs_greedy"] - 1) * 100
    vs_eft = (1 - 1 / r["ratios"]["erls_vs_eft"]) * 100
    lines.append(f"online/erls_vs_greedy,{per:.0f},improvement_pct={vs_greedy:.2f}")
    lines.append(f"online/erls_vs_eft,{per:.0f},deficit_pct={vs_eft:.2f}")
    print(f"# online: {r['runs']} runs in {dt:.1f}s | mean ratios "
          f"ER-LS={r['ratios']['er_ls']:.3f} EFT={r['ratios']['eft']:.3f} "
          f"Greedy={r['ratios']['greedy']:.3f} Random={r['ratios']['random']:.3f}")
    print(f"#   paper claims: ER-LS ~16% better than Greedy -> measured "
          f"{vs_greedy:+.1f}%; EFT ~10% better than ER-LS -> measured {vs_eft:+.1f}%")
    for s, d in r["curve"].items():
        print(f"#   curve sqrt(m/k)={s}: ER-LS={d['er_ls']:.3f} (bound 4*{s})")
    return lines


def bench_sim(full: bool, seed: int = 0) -> list[str]:
    """Unified repro.sim sweep: all adapters × scenario families × noise."""
    from . import campaign
    with obs.timer("bench.sim") as sp:
        r = campaign.sim_sweep(full=full, base_seed=seed)
    dt = sp.dur
    per = dt / max(r["runs"], 1) * 1e6
    lines = []
    for alg in r["schedulers"]:
        lines.append(f"sim/{alg},{per:.0f},"
                     f"mean_ratio_lb={r['ratios'][alg]:.4f};"
                     f"noise_degrade={r['ratios']['degrade_' + alg]:.4f}")
    gain = (r["ratios"]["heft_comm_gain"] - 1) * 100
    lines.append(f"sim/heft_comm_gain,{per:.0f},oblivious_penalty_pct={gain:.2f}")
    again = (r["ratios"]["cahlp_comm_gain"] - 1) * 100
    nbgain = (r["ratios"]["cahlp_netbound_gain"] - 1) * 100
    lines.append(f"sim/cahlp_comm_gain,{per:.0f},oblivious_penalty_pct={again:.2f};"
                 f"netbound_pct={nbgain:.2f}")
    wgain = (r["ratios"]["mhlp_width_gain"] - 1) * 100
    lines.append(f"sim/mhlp_width_gain,{per:.0f},width1_penalty_pct={wgain:.2f}")
    cmgain = (r["ratios"]["camhlp_comm_gain"] - 1) * 100
    lines.append(f"sim/camhlp_comm_gain,{per:.0f},oblivious_penalty_pct={cmgain:.2f}")
    ctgain = (r["ratios"]["contention_gap"] - 1) * 100
    spread = (r["ratios"]["net_maxmin_fair_hlp_ols"]
              / r["ratios"]["net_instant_hlp_ols"] - 1) * 100
    lines.append(f"sim/contention_gap,{per:.0f},oblivious_penalty_pct={ctgain:.2f};"
                 f"netmodel_spread_pct={spread:.2f}")
    import jax
    bucket_s = sum(r["phase_seconds"].values())
    throughput = r["evals"] / max(bucket_s, 1e-9)
    per_device = throughput / max(jax.device_count(), 1)
    lines.append(f"sim/throughput_plans_per_sec,{per:.0f},"
                 f"plans_per_sec={throughput:.1f};"
                 f"per_device={per_device:.1f}")
    lines.append(f"sim/plan_build_s,{per:.0f},"
                 f"plan_build_s={r['plan_build_s']:.3f};"
                 f"overlap_frac={r['overlap_frac']:.3f};"
                 f"workers={r['plan_workers']}")
    lines.append(f"sim/plan_cache,{per:.0f},"
                 f"hits={r['plan_cache_hits']};"
                 f"misses={r['plan_cache_misses']};"
                 f"hit_rate={r['plan_cache_hit_rate']:.3f}")
    BENCH_EXTRAS["sim"] = {
        "phase_seconds": r["phase_seconds"],
        "compiles": r["compiles"],
        "contended_compiles": r["contended_compiles"],
        "plans": r["plans"],
        "evals": r["evals"],
        "runs": r["runs"],
        "scenarios": r["scenarios"],
        "throughput_plans_per_sec": throughput,
        "throughput_plans_per_sec_per_device": per_device,
        "plan_build_s": r["plan_build_s"],
        "overlap_frac": r["overlap_frac"],
        "plan_cache_hits": r["plan_cache_hits"],
        "plan_cache_misses": r["plan_cache_misses"],
        "plan_cache_hit_rate": r["plan_cache_hit_rate"],
        "plan_workers": r["plan_workers"],
        "metrics": r["ratios"],
    }
    print(f"# sim: {r['runs']} runs over {r['scenarios']} scenarios in "
          f"{dt:.1f}s | {r['plans']} static plans in {r['compiles']} XLA "
          f"compiles (bucketed, +{r['contended_compiles']} contended) | "
          f"{throughput:.0f} plan-evals/s over the bucketed phases | "
          f"LB ratios " +
          " ".join(f"{a}={r['ratios'][a]:.3f}" for a in r["schedulers"]))
    print("#   noise degradation (noisy/clean): " +
          " ".join(f"{a}={r['ratios']['degrade_' + a]:.3f}"
                   for a in r["schedulers"]))
    print(f"#   comm-aware HEFT vs oblivious: oblivious pays {gain:+.1f}% "
          f"(mean over comm scenarios; engine charges comm either way)")
    print(f"#   comm-aware *allocation*: oblivious HLP pays {again:+.1f}% "
          f"mean makespan vs CAHLP on the comm scenarios — {nbgain:+.1f}% "
          f"on the netbound family (the LP sees the network)")
    print(f"#   moldable: width-1 HLP pays {wgain:+.1f}% mean makespan vs "
          f"width-aware MHLP on the moldable_cholesky family; oblivious "
          f"MHLP pays {cmgain:+.1f}% vs CAMHLP under transfers")
    print(f"#   network models (netbound): maxmin_fair costs hlp_ols "
          f"{spread:+.1f}% over instant; under contention the oblivious "
          f"allocation pays {ctgain:+.1f}% vs the load-priced LP")
    print(f"#   pipelined executor: {r['plan_build_s']:.2f}s of solver time "
          f"over {r['plan_workers']} worker(s), overlap_frac="
          f"{r['overlap_frac']:.2f}, plan cache {r['plan_cache_hits']}/"
          f"{r['plan_cache_hits'] + r['plan_cache_misses']} hits "
          f"(rate {r['plan_cache_hit_rate']:.2f})")
    return lines


def bench_search(full: bool, seed: int = 0) -> list[str]:
    """Population-based plan search vs the LP+OLS pipeline (repro.search):
    the ``sim/evo_gap`` headline is how much makespan the best heuristic
    seed leaves on the table against the evolved plan at n ≈ 50–500."""
    from . import campaign
    with obs.timer("bench.search") as sp:
        r = campaign.search_sweep(full=full, base_seed=seed)
    dt = sp.dur
    per = dt / max(r["cells"], 1) * 1e6
    gap = (r["ratios"]["evo_gap"] - 1) * 100
    lines = [f"sim/evo_gap,{per:.0f},seed_excess_pct={gap:.2f};"
             f"mean_ratio={r['ratios']['evo_gap']:.4f}"]
    lines.append(f"search/evo_vs_lb,{per:.0f},"
                 f"mean_ratio_lb={r['ratios']['evo_vs_lb']:.4f}")
    lines.append(f"search/lp_vs_evo,{per:.0f},"
                 f"lp_excess_pct={(r['ratios']['lp_vs_evo'] - 1) * 100:.2f}")
    lines.append(f"search/anytime_gain,{per:.0f},"
                 f"beyond_gen0_pct={(r['ratios']['anytime_gain'] - 1) * 100:.2f}")
    for meth in ("cem", "sa"):
        lines.append(f"search/{meth}_vs_ga,{per:.0f},"
                     f"ratio={r['ratios'][f'{meth}_vs_ga']:.4f}")
    search_s = sum(r["phase_seconds"].values())
    throughput = r["evals"] / max(search_s, 1e-9)
    lines.append(f"search/throughput_evals_per_sec,{per:.0f},"
                 f"evals_per_sec={throughput:.1f}")
    BENCH_EXTRAS["search"] = {
        "phase_seconds": r["phase_seconds"],
        "compiles": r["compiles"],
        "buckets": r["buckets"],
        "cells": r["cells"],
        "max_n": r["max_n"],
        "evals": r["evals"],
        "cache_hits": r["cache_hits"],
        "throughput_evals_per_sec": throughput,
        "metrics": r["ratios"],
    }
    print(f"# search: {r['cells']} (scenario × seed) cells up to "
          f"n={r['max_n']} in {dt:.1f}s | {r['evals']} genome evals "
          f"(+{r['cache_hits']} cache hits) in {r['compiles']} XLA compiles "
          f"over {r['buckets']} shape buckets | {throughput:.0f} evals/s")
    print(f"#   evo_gap: best heuristic seed pays {gap:+.2f}% mean makespan "
          f"vs the evolved plan (anytime-no-worse by construction; "
          f"LP+OLS leaves {(r['ratios']['lp_vs_evo'] - 1) * 100:+.2f}%)")
    print(f"#   methods on {('full' if full else 'quick')} scenario 0: "
          f"cem/ga={r['ratios']['cem_vs_ga']:.4f} "
          f"sa/ga={r['ratios']['sa_vs_ga']:.4f} (<1 beats the GA)")
    return lines


def bench_streams(full: bool, seed: int = 0) -> list[str]:
    """Open-system streams: (arrival process × policy × seed) grid with
    per-tenant bounded slowdown, utilization, and rollout compile count."""
    from . import campaign
    with obs.timer("bench.streams") as sp:
        r = campaign.streams_campaign(full=full, base_seed=seed)
    dt = sp.dur
    per = dt / max(r["runs"], 1) * 1e6
    lines = []
    for proc in r["processes"]:
        for pol in r["policies"]:
            lines.append(f"streams/{proc}_{pol},{per:.0f},"
                         f"mean_slowdown={r['mean_slowdown'][(proc, pol)]:.4f}")
    edge = (r["sitl_vs_erls_bursty"] - 1) * 100
    lines.append(f"streams/sitl_vs_erls_bursty,{per:.0f},"
                 f"erls_excess_pct={edge:.2f}")
    print(f"# streams: {r['runs']} stream runs ({r['jobs']} jobs) in {dt:.1f}s"
          f" | rollout path: {r['compiles']} XLA compiles")
    for proc in r["processes"]:
        print(f"#   {proc}: " + " ".join(
            f"{pol}={r['mean_slowdown'][(proc, pol)]:.3f}"
            for pol in r["policies"]))
    print(f"#   sim-in-the-loop vs ER-LS on bursty: ER-LS pays {edge:+.1f}% "
          f"mean bounded slowdown")
    return lines


def bench_roofline(full: bool, seed: int = 0) -> list[str]:
    """Summarize dry-run roofline artifacts (produced by repro.launch.dryrun)."""
    art = os.path.join(os.path.dirname(__file__), "..", "artifacts",
                       "dryrun_results.jsonl")
    if not os.path.exists(art):
        print("# roofline: no artifacts/dryrun_results.jsonl "
              "(run: python -m repro.launch.dryrun)")
        return []
    lines = []
    with open(art) as f:
        recs = [json.loads(line) for line in f if line.strip()]
    ok = [r for r in recs if r.get("status") == "ok"]
    print(f"# roofline: {len(ok)}/{len(recs)} dry-run cells ok")
    for r in ok:
        if r.get("mesh") != "single_pod":
            continue
        terms = r["roofline"]
        dom = max(("compute", "memory", "collective"),
                  key=lambda k: terms[f"{k}_s"])
        lines.append(
            f"roofline/{r['arch']}/{r['shape']},{terms['compute_s'] * 1e6:.0f},"
            f"dominant={dom};frac={terms['roofline_fraction']:.3f}")
    return lines


def bench_solver(full: bool, seed: int = 0) -> list[str]:
    """Allocation-phase runtime: exact HiGHS LP vs the jitted JAX solver
    (the paper reports ~100 s GLPK solves on its largest instances)."""
    from repro.core.hlp import solve_hlp
    from repro.core.hlp_jax import solve_hlp_jax
    from repro.core.workloads import chameleon
    lines = []
    insts = [("potrf", 10), ("getrf", 10)] + ([("potri", 20)] if full else [])
    for app, nb in insts:
        g = chameleon(app, nb, 512)
        with obs.timer(f"bench.solver.exact.{app}{nb}") as sp_e:
            exact = solve_hlp(g, 64, 8)
        with obs.timer(f"bench.solver.jax.{app}{nb}") as sp_j:
            approx = solve_hlp_jax(g, 64, 8, iters=300)
        t0, t1, t2 = 0.0, sp_e.dur, sp_e.dur + sp_j.dur
        gap = (approx.lp_value / exact.lp_value - 1) * 100
        lines.append(f"solver/{app}{nb}_exact,{(t1-t0)*1e6:.0f},lp={exact.lp_value:.4f}")
        lines.append(f"solver/{app}{nb}_jax,{(t2-t1)*1e6:.0f},gap_pct={gap:.3f}")
        print(f"# solver {app}{nb} (n={g.n}): HiGHS {t1-t0:.2f}s, "
              f"JAX {t2-t1:.2f}s (incl. jit), gap {gap:.2f}%")
    return lines


def bench_kernels(full: bool, seed: int = 0) -> list[str]:
    from . import kernel_bench
    lines = kernel_bench.run(full)
    # land the kernel timings in the BENCH_sim.json trajectory: parse the
    # ``name,us_per_call,derived`` lines back into structured numbers
    timings = {}
    for line in lines:
        parts = line.split(",")
        if len(parts) >= 2:
            try:
                timings[parts[0]] = float(parts[1])
            except ValueError:
                pass
    BENCH_EXTRAS["kernels"] = {"us_per_call": timings}
    return lines


BENCHES = {
    "offline2": bench_offline2,
    "offline3": bench_offline3,
    "online": bench_online,
    "sim": bench_sim,
    "search": bench_search,
    "streams": bench_streams,
    "solver": bench_solver,
    "kernels": bench_kernels,
    "roofline": bench_roofline,
}


def list_registry() -> None:
    """Print the (scheduler × scenario family × platform) registry — read
    straight from the v2 allocation API, not a hand-maintained list."""
    from repro.platform import PLATFORMS
    from repro.sim.adapters import ADAPTERS
    from repro.sim.scenarios import SCENARIO_FAMILIES

    print("schedulers (repro.sim.adapters.ADAPTERS):")
    for name in sorted(ADAPTERS):
        print(f"  {name}")
    print("scenario families (repro.sim.scenarios.SCENARIO_FAMILIES):")
    for name in sorted(SCENARIO_FAMILIES):
        print(f"  {name}")
    print("platforms (repro.platform.PLATFORMS):")
    for name, p in PLATFORMS.items():
        pools = " ".join(f"{nm}={c}" for nm, c in zip(p.names, p.counts))
        print(f"  {name}: {pools}")
    print("campaigns (benchmarks.run):")
    for name in BENCHES:
        print(f"  {name}")


def _host_info() -> dict:
    """The execution substrate a trajectory was measured on — what makes
    two BENCH_sim.json files comparable (or explains why they aren't)."""
    import platform as _platform

    import jax

    from repro.sim import campaign_mesh, contention_kernel, shard_backend

    return {
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "mesh_shape": {k: int(v) for k, v in campaign_mesh().shape.items()},
        "shard_backend": shard_backend(),
        "contention_kernel": contention_kernel(),
        "jax": jax.__version__,
        "python": _platform.python_version(),
    }


def write_bench_json(path: str, args, names: list[str],
                     benches: dict[str, dict],
                     obs_section: dict | None = None) -> None:
    """Write the ``repro.bench.v1`` perf trajectory.

    Schema (stable — ``render_tables --diff-bench`` and the CI pinned-value
    check parse it):

    * ``schema``: the literal ``"repro.bench.v1"``.
    * ``run``: {seed, full, targets} — the harness invocation.
    * ``host``: backend / device_count / mesh_shape / shard_backend /
      contention_kernel / jax / python.
    * ``benches.<name>``: {wall_s, lines, ...extras} — every target gets
      its wall-clock and raw CSV lines; ``sim`` adds phase_seconds,
      compile counts, plans/evals, throughput_plans_per_sec(_per_device),
      the pipelined-executor fields (plan_build_s, overlap_frac,
      plan_cache_hits/misses/hit_rate, plan_workers) and the ``metrics``
      ratio dict (the diffable makespan metrics); ``kernels`` adds its
      us_per_call timings.

    A partial-target run (``--only sim``) must not clobber the sections an
    earlier run wrote: when the file already holds a same-(seed, full)
    ``repro.bench.v1`` doc, its other benches are carried over and
    ``run.targets`` becomes the union.  A different seed/full (or a
    corrupt file) overwrites — those sections wouldn't be comparable.
    """
    carried: dict[str, dict] = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                old = json.load(f)
        except (OSError, ValueError):
            old = None
        if (isinstance(old, dict) and old.get("schema") == "repro.bench.v1"
                and old.get("run", {}).get("seed") == args.seed
                and old.get("run", {}).get("full") == bool(args.full)):
            carried = {k: v for k, v in old.get("benches", {}).items()
                       if k not in benches}
    if carried:
        benches = {**carried, **benches}
        names = sorted(set(names) | set(carried))
    doc = {
        "schema": "repro.bench.v1",
        "run": {"seed": args.seed, "full": bool(args.full), "targets": names},
        "host": _host_info(),
        "benches": benches,
    }
    if obs_section is not None:
        doc["obs"] = obs_section
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"# wrote {path}"
          + (f" (kept earlier benches: {','.join(sorted(carried))})"
             if carried else ""))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="full §6 grid (nb=20, all block sizes, 64 3-type configs)")
    ap.add_argument("--only", type=str, default="",
                    help="comma-separated subset of: " + ",".join(BENCHES))
    ap.add_argument("--seed", type=int, default=0,
                    help="base seed for the campaign grids (sim + streams): "
                         "shifts every scenario/stream generator seed")
    ap.add_argument("--list", action="store_true",
                    help="print the (scheduler × scenario × platform) "
                         "registry and exit")
    ap.add_argument("--bench-json", type=str,
                    default=os.path.join(os.path.dirname(__file__), "..",
                                         "artifacts", "BENCH_sim.json"),
                    help="where to write the repro.bench.v1 perf trajectory "
                         "(empty string disables)")
    ap.add_argument("--trace", type=str, default="",
                    help="directory for Perfetto-loadable chrome traces: "
                         "enables repro.obs and writes trace_<bench>.json "
                         "(wall-clock spans) plus decisions_<bench>.json "
                         "(per-task allocation provenance) per target")
    args = ap.parse_args()
    if args.list:
        list_registry()
        return
    names = [n for n in args.only.split(",") if n] or list(BENCHES)
    unknown = [n for n in names if n not in BENCHES]
    if unknown:
        print(f"unknown --only target(s): {','.join(unknown)}; "
              f"have {','.join(BENCHES)}", file=sys.stderr)
        sys.exit(2)
    print(f"# benchmarks.run: targets={','.join(names)} full={args.full} "
          f"base_seed={args.seed}", flush=True)
    from repro.sim import configure_xla_cache
    # warm runs skip recompiling the bucketed kernels
    print(f"# xla compilation cache: {configure_xla_cache()}", flush=True)
    if args.trace:
        obs.enable()
        os.makedirs(args.trace, exist_ok=True)
    all_lines = ["name,us_per_call,derived"]
    failed: list[str] = []
    benches: dict[str, dict] = {}
    trace_files: dict[str, str] = {}
    for name in names:
        print(f"== {name} ==", flush=True)
        if args.trace:
            obs.reset()   # fresh span/decision buffers per target
                          # (counters stay cumulative across the run)
        with obs.timer(f"run.{name}") as sp:
            try:
                lines = BENCHES[name](args.full, args.seed)
                all_lines += lines
                benches[name] = {"wall_s": sp.elapsed(),
                                 "lines": lines, **BENCH_EXTRAS.get(name, {})}
            except Exception as e:  # finish the harness; don't hide the loss
                print(f"# {name} FAILED: {type(e).__name__}: {e}")
                all_lines.append(f"{name},0,FAILED")
                failed.append(name)
                benches[name] = {"wall_s": sp.elapsed(),
                                 "lines": [], "failed": True}
        if args.trace:
            tpath = os.path.join(args.trace, f"trace_{name}.json")
            obs.export_chrome_trace(tpath, obs.wall_trace_events())
            trace_files[name] = tpath
            print(f"# wrote {tpath}")
            recs = obs.decision_records()
            if recs:
                dpath = os.path.join(args.trace, f"decisions_{name}.json")
                obs.dump_decisions(dpath, recs)
                print(f"# wrote {dpath}")
    print("\n".join(all_lines))
    obs_section = None
    if args.trace:
        obs_section = {"counters": obs.counters(), "gauges": obs.gauges(),
                       "traces": trace_files}
        ctrs = " ".join(f"{k}={v}" for k, v in sorted(obs.counters().items()))
        print(f"# obs: {ctrs}")
    if args.bench_json:
        write_bench_json(args.bench_json, args, names, benches, obs_section)
    if failed:   # CI must see a red exit when any sub-campaign raised
        print(f"# FAILED sub-campaigns: {','.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
