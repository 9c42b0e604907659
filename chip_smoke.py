#!/usr/bin/env python3
"""Drive the scheduler's device path once on a TPU and check every result.

    python chip_smoke.py              # one chip: all four phases, §6 scale
    python chip_smoke.py --chips 4    # four chips: sharded vs 1-device buckets
    python chip_smoke.py --rehearse   # any backend, tiny sizes, no verdict

Phases, all in this one process (an accelerator belongs to one process; the
plan-construction pool's workers stay on JAX's CPU backend):

  closed     Chameleon potrf and getrf at nb = 20 on 128 CPUs + 16 GPUs
             (the paper's §6 scale), four allocators, one clean row plus 32
             lognormal noise seeds, through ``pipelined_sweep_makespans``
             with its default worker pool.  Reference: the event engine
             (``repro.sim.simulate``), rtol 1e-5; at most one bucket compile
             per shape bucket.
  contended  The netbound grid of the ``sim`` campaign (six seeds, hlp_ols
             and the contention-priced CAHLP, under instant / fixed_latency /
             maxmin_fair per-entry networks).  Reference: the numpy fluid
             oracle ``contended_plan_delays`` for the jitted float64 kernel's
             per-edge delays, to rtol 1e-6 (the CPU bound: the TPU's
             emulated float64 meets it), and the event engine for the
             makespans, to rtol 1e-5.
  streams    ``run_stream`` with simulation-in-the-loop over a bursty MMPP
             stream of 64 Cholesky/LU jobs on 64 CPUs + 8 GPUs: every job
             completes, the run validates, and rollouts compile at most once
             per rollout envelope.
  kernels    The Pallas ``maxplus_matmul`` (2048², f32) and
             ``flash_attention_bhsd`` ((16, 2048, 128), bf16) compiled for
             the chip — never interpreted — against their ``ref.py`` oracles
             at the tolerances of ``tests/test_kernels.py``.

``--chips 4`` runs only the closed phase's buckets, once sharded over the
four-device campaign mesh and once on a one-device mesh, and requires
bit-identical makespans.

Every graph and stream is generated from seeds.  Each phase prints one
``# <phase>:`` line (wall and compile seconds, compile counts, persistent
cache hits, plans, evaluations, worst error against its reference).  The
last line of a passing chip run is one JSON object naming the device; any
failed check raises, and the script then exits non-zero without it.  With
no TPU present it exits non-zero before running anything.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

NOISE_SCALE = 0.2
SEED = 0

#: Phase sizes: the chip run's, and the rehearsal's (same code paths at a
#: size a CPU interprets in seconds).
SIZES = {
    "chip": dict(nb=20, counts=(128, 16), noise_seeds=32, netbound_seeds=6,
                 jobs=64, job_nb=12, stream_counts=(64, 8), mm=2048,
                 attn=(16, 2048, 128)),
    "rehearse": dict(nb=4, counts=(8, 2), noise_seeds=4, netbound_seeds=6,
                     jobs=8, job_nb=4, stream_counts=(8, 2), mm=256,
                     attn=(2, 256, 128)),
}


class CheckFailed(RuntimeError):
    """A phase's result missed its reference."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


class CompileMeter:
    """XLA compile seconds and persistent-cache hits/misses, summed from
    JAX's monitoring events (a cache hit still reports a backend compile
    event: the time it took to load the executable)."""

    def __init__(self):
        import jax

        self._lock = threading.Lock()
        self.seconds, self.compiles, self.hits, self.misses = 0.0, 0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.seconds += duration
                self.compiles += 1

    def _event(self, event, **_):
        with self._lock:
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

    def snapshot(self) -> tuple:
        with self._lock:
            return self.seconds, self.compiles, self.hits, self.misses


def run_phase(name: str, fn, meter: CompileMeter) -> dict:
    c0 = meter.snapshot()
    t0 = time.perf_counter()
    out = fn()
    wall = time.perf_counter() - t0
    c1 = meter.snapshot()
    fields = {"wall_s": wall, "compile_s": c1[0] - c0[0],
              "xla_compiles": c1[1] - c0[1], "cache_hits": c1[2] - c0[2],
              "cache_misses": c1[3] - c0[3], **out}
    print(f"# {name}: " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)
    return fields


def rel_err(got, want) -> float:
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want) / np.maximum(np.abs(want), 1e-300),
                        initial=0.0))


def noise_grid(noise_seeds: int):
    """Clean row + seeded lognormal rows — the campaign's sample grid; row
    s + 1 draws the stream ``simulate(..., seed=s)`` draws."""
    import numpy as np

    from repro.sim import NoiseModel
    from repro.sim.batch import sample_actual_batch

    noise = NoiseModel("lognormal", NOISE_SCALE)
    seeds = list(range(noise_seeds))

    def grid(g, plan):
        return np.vstack([sample_actual_batch(g, plan, NoiseModel(), [0]),
                          sample_actual_batch(g, plan, noise, seeds)])
    return grid


def closed_entries(size: dict) -> list:
    from repro.sim import make_scheduler
    from repro.sim.scenarios import cholesky_scenario, lu_scenario

    kw = dict(nb_blocks=size["nb"], block_size=512, counts=size["counts"],
              ccr=0.5, seed=SEED)
    potrf, getrf = cholesky_scenario(**kw), lu_scenario(**kw)
    grid = (("hlp_ols", "heft", "hlp_jax_ols", "cahlp_ols"),
            ("hlp_ols", "heft", "hlp_jax_ols"))
    return [(sc.graph, sc.machine, make_scheduler(name))
            for sc, names in zip((potrf, getrf), grid) for name in names]


# ------------------------------------------------------------------ phases
def phase_closed(size: dict) -> dict:
    from repro.sim import NoiseModel, simulate
    from repro.sim.adapters import FrozenPlanScheduler
    from repro.sim.batch import reset_trace_counts, search_envelope, \
        trace_count
    from repro.sim.pipeline import cached_allocate, last_pipeline_stats, \
        pipelined_sweep_makespans

    entries = closed_entries(size)
    reset_trace_counts()
    sweeps = pipelined_sweep_makespans(entries,
                                       sample_fn=noise_grid(size["noise_seeds"]))
    stats = last_pipeline_stats()
    buckets = len({search_envelope(g, m) for g, m, _ in entries})
    traces = trace_count("bucket")
    check(traces <= buckets, f"{traces} bucket compiles for {buckets} buckets")

    noise = NoiseModel("lognormal", NOISE_SCALE)
    worst = 0.0
    for (g, machine, sched), ms in zip(entries, sweeps):
        plan = cached_allocate(sched, g, machine)   # the plan evaluated
        frozen = FrozenPlanScheduler(plan, name=sched.name)
        clean = simulate(g, machine, frozen, seed=0).makespan
        noisy = simulate(g, machine, frozen, noise=noise, seed=0).makespan
        err = rel_err(ms[:2], [clean, noisy])
        check(err <= 1e-5, f"{sched.name} on n={g.n}: makespan off the "
                           f"event engine by rtol {err}")
        worst = max(worst, err)
    return {"tasks": f"{entries[0][0].n}+{entries[-1][0].n}",
            "plans": len(entries), "evals": sum(s.size for s in sweeps),
            "buckets": buckets, "bucket_traces": traces,
            "plan_workers": stats.workers, "plan_build_s": stats.plan_build_s,
            "worst_rel_err": worst, "tol": 1e-5}


def phase_contended(size: dict) -> dict:
    import numpy as np

    from repro.sim import make_network, make_scheduler, plan_times, simulate
    from repro.sim.adapters import CommAwareHLPScheduler, FrozenPlanScheduler
    from repro.sim.batch import contended_bucket_delays, reset_trace_counts, \
        search_envelope, trace_count
    from repro.sim.network import contended_plan_delays, plan_transfers
    from repro.sim.pipeline import cached_allocate, pipelined_sweep_makespans
    from repro.sim.scenarios import netbound_scenario

    nets = {name: make_network(name)
            for name in ("instant", "fixed_latency", "maxmin_fair")}
    allocs = (lambda: make_scheduler("hlp_ols"),
              lambda: CommAwareHLPScheduler(contention=True))
    entries, networks = [], []
    for i in range(size["netbound_seeds"]):
        sc = netbound_scenario(seed=300 + i)
        for mk in allocs:
            for net in nets.values():
                entries.append((sc.graph, sc.machine, mk()))
                networks.append(net)
    reset_trace_counts()
    sweeps = pipelined_sweep_makespans(
        entries, sample_fn=noise_grid(size["noise_seeds"]), networks=networks)
    traces = trace_count("contended")

    maxmin = nets["maxmin_fair"]
    worst_delay = worst_ms = 0.0
    contended = []
    for (g, machine, sched), net, ms in zip(entries, networks, sweeps):
        plan = cached_allocate(sched, g, machine)
        if net is maxmin:
            contended.append((g, machine, plan, float(ms[0])))
            continue
        ref = simulate(g, machine, FrozenPlanScheduler(plan), network=net,
                       seed=0).makespan
        worst_ms = max(worst_ms, rel_err(ms[0], ref))
    # the kernel compiles once per (shape bucket, link count) envelope
    transfers = [(search_envelope(g, machine), plan_transfers(g, plan, maxmin))
                 for g, machine, plan, _ in contended]
    envelopes = {(env, tr.num_links) for env, tr in transfers if tr.count}
    check(traces <= len(envelopes),
          f"{traces} contended compiles for {len(envelopes)} envelopes")
    kernel = contended_bucket_delays([(g, p) for g, _, p, _ in contended],
                                     [maxmin] * len(contended))
    check(trace_count("contended") == traces,
          "the contended kernel retraced on shapes the grid had compiled")
    for (g, machine, plan, ms), got in zip(contended, kernel):
        want = contended_plan_delays(g, plan, plan_times(g, plan, g.proc),
                                     maxmin)
        err = rel_err(got[want > 0], want[want > 0])
        check(np.allclose(got, want, rtol=1e-6, atol=1e-9),
              f"contended delays off the numpy oracle by rtol {err}")
        worst_delay = max(worst_delay, err)
        # the oracle's delays as fixed edge costs, replayed by the engine
        ref = simulate(g.with_comm(want), machine, FrozenPlanScheduler(plan),
                       seed=0).makespan
        worst_ms = max(worst_ms, rel_err(ms, ref))
    check(worst_ms <= 1e-5, f"contended makespans off by rtol {worst_ms}")
    return {"plans": len(entries), "evals": sum(s.size for s in sweeps),
            "contended_plans": len(contended), "contended_traces": traces,
            "contended_envelopes": len(envelopes),
            "worst_delay_rel_err": worst_delay, "delay_tol": 1e-6,
            "worst_makespan_rel_err": worst_ms, "makespan_tol": 1e-5}


def phase_streams(size: dict) -> dict:
    from repro.platform import Platform
    from repro.sim import NoiseModel
    from repro.sim.batch import reset_trace_counts, search_envelope, \
        trace_count
    from repro.streams import JobFactory, MMPPProcess, SimInTheLoop, \
        open_stream, run_stream

    machine = Platform.hybrid(*size["stream_counts"])
    job = dict(nb_blocks=size["job_nb"], block_size=512)
    factory = JobFactory(("cholesky", "lu"),
                         params={"cholesky": job, "lu": job})
    source = open_stream(MMPPProcess(rates=(0.01, 0.15), dwell=(60.0, 25.0)),
                         factory, num_jobs=size["jobs"], num_tenants=4,
                         seed=SEED)
    envelopes = {search_envelope(j.graph, machine)
                 for j in source.initial_jobs()}
    reset_trace_counts()
    policy = SimInTheLoop()
    res = run_stream(source, machine, policy,
                     noise=NoiseModel("lognormal", NOISE_SCALE), seed=SEED,
                     validate=True)
    traces = trace_count("bucket")
    done = sorted(r.jid for r in res.jobs)
    check(done == list(range(size["jobs"])),
          f"{len(done)} of {size['jobs']} jobs completed")
    check(traces <= len(envelopes),
          f"{traces} rollout compiles for {len(envelopes)} envelopes")
    return {"jobs": len(done), "tasks": sum(r.n_tasks for r in res.jobs),
            "rollouts": len(policy.decisions), "rollout_traces": traces,
            "rollout_envelopes": len(envelopes),
            "mean_slowdown": res.mean_slowdown()}


def phase_kernels(size: dict, require_compiled: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import resolve_interpret
    from repro.kernels.flash_attention.flash_attention import \
        flash_attention_bhsd
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.maxplus.maxplus import maxplus_matmul
    from repro.kernels.maxplus.ref import maxplus_matmul_ref

    interpret = resolve_interpret(None)
    check(not (require_compiled and interpret),
          "Pallas would run in interpret mode on this backend")
    rng = np.random.default_rng(SEED)
    out = {"interpret": interpret}

    def run(name, fn, args, ref_fn, tol):
        compiled = fn.lower(*args).compile()
        custom = "tpu_custom_call" in compiled.as_text()
        check(custom or not require_compiled,
              f"{name}: no Mosaic kernel in the compiled program")
        got = np.asarray(compiled(*args), np.float32)
        want = np.asarray(jax.jit(ref_fn)(*args), np.float32)
        err = float(np.abs(got - want).max())
        check(np.allclose(got, want, atol=tol, rtol=tol),
              f"{name}: off ref.py by {err}")
        out[f"{name}_compiled"] = custom
        out[f"{name}_max_abs_err"] = err
        out[f"{name}_normwise_rel_err"] = err / float(np.abs(want).max())
        out[f"{name}_tol"] = tol

    m = size["mm"]
    a, b = (jnp.asarray(rng.normal(size=(m, m)).astype(np.float32))
            for _ in range(2))
    run(f"maxplus_{m}", maxplus_matmul, (a, b), maxplus_matmul_ref, 1e-5)
    q, k, v = (jnp.asarray(rng.normal(size=size["attn"]), jnp.bfloat16)
               for _ in range(3))
    run("flash_" + "x".join(map(str, size["attn"])), flash_attention_bhsd,
        (q, k, v), lambda *x: attention_ref(*x, causal=True), 2e-2)
    return out


def phase_sharded(size: dict) -> dict:
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.sim.batch import campaign_mesh, set_campaign_mesh
    from repro.sim.pipeline import pipelined_sweep_makespans

    entries = closed_entries(size)
    grid = noise_grid(size["noise_seeds"])
    mesh = campaign_mesh()
    check(mesh.devices.size == 4, f"campaign mesh spans {mesh.devices.size} "
                                  "devices, not 4")
    sharded = pipelined_sweep_makespans(entries, sample_fn=grid)
    set_campaign_mesh(Mesh(np.asarray(jax.devices()[:1]), ("plans",)))
    try:
        single = pipelined_sweep_makespans(entries, sample_fn=grid)
    finally:
        set_campaign_mesh(None)
    same = all(np.array_equal(a, b) for a, b in zip(sharded, single))
    check(same, "sharded makespans differ from the one-device mesh")
    return {"plans": len(entries), "evals": sum(s.size for s in sharded),
            "mesh_devices": int(mesh.devices.size), "bit_identical": same}


# --------------------------------------------------------------------- main
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded-vs-one-device closed buckets")
    ap.add_argument("--rehearse", action="store_true",
                    help="run every phase at a tiny size on whatever "
                         "backend is present; prints no verdict line")
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"# device: {device}", flush=True)
    if not args.rehearse and dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    if device["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {device['count']} "
              f"device(s)", file=sys.stderr)
        return 1

    from repro.sim import configure_xla_cache

    print(f"# xla compilation cache: {configure_xla_cache()}", flush=True)
    meter = CompileMeter()
    size = SIZES["rehearse" if args.rehearse else "chip"]
    if args.chips == 4:
        run_phase("sharded", lambda: phase_sharded(size), meter)
    else:
        run_phase("closed", lambda: phase_closed(size), meter)
        run_phase("contended", lambda: phase_contended(size), meter)
        run_phase("streams", lambda: phase_streams(size), meter)
        run_phase("kernels", lambda: phase_kernels(
            size, require_compiled=not args.rehearse), meter)
    if args.rehearse:
        print("# rehearsal passed: not a chip run, no verdict", flush=True)
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
