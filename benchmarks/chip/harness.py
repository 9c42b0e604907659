"""One run of one cell: set-up, a measured window, the check, the line.

Everything a cell is made of is found by name: ``BENCHMARK.json`` names
the cell's configuration, traffic mix and metrics; the configuration is a
JSON file (its ``file``), the traffic mix is ``traffic/<name>.json``, and
each metric is read by ``metrics/<name>.py``.  The traffic file names the
general player (``players/<player>.py``) that plays it.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
import sys
import time
from dataclasses import dataclass, field

import numpy as np

from . import device as _device
from .trace import Tracer, reduce

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCHMARK_FILE = os.path.join(ROOT, "BENCHMARK.json")


# ------------------------------------------------------------- the spec
def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _rehearsed(spec: dict) -> dict:
    """A config or traffic mix with its ``rehearsal`` sizes put in."""
    out = {k: v for k, v in spec.items() if k != "rehearsal"}
    out.update(spec.get("rehearsal", {}))
    return out


@dataclass
class Cell:
    """One entry of ``workloads``, with everything it names loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    metrics: dict = field(default_factory=dict)   # name -> BENCHMARK entry


def load_cell(workload: str, bench_file: str = BENCHMARK_FILE,
              rehearse: bool = False) -> Cell:
    bench = load_json(bench_file)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in {bench_file}; "
                       f"have {sorted(cells)}")
    w = cells[workload]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = load_json(os.path.join(ROOT, cfg["file"]))
    traffic = load_json(os.path.join(HERE, "traffic", w["traffic"] + ".json"))
    if rehearse:
        config, traffic = _rehearsed(config), _rehearsed(traffic)
    cell = Cell(workload, int(w["chips"]), config, traffic)
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            if workload in m.get("workloads", [workload]):
                cell.metrics[m["name"]] = dict(m, kind=kind)
    return cell


def load_metric(name: str):
    """The ``read(run)`` function of ``metrics/<name>.py``."""
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_player(name: str):
    return importlib.import_module(f"{__package__}.players.{name}")


# ------------------------------------------------------------ the window
class WindowClosed(Exception):
    """Raised by a player that stops a step when the window has closed."""


class Window:
    """The measured window.  Players call ``tick()`` between units of work
    (steps, or arrivals inside a long step); it starts and stops the trace
    on those boundaries and says when the window has closed."""

    def __init__(self, seconds: float, tracer: Tracer | None = None,
                 trace_seconds: float = 3.0):
        self.seconds = float(seconds)
        self.tracer = tracer
        self.trace_seconds = float(trace_seconds)
        self.t0 = self.t_end = None
        self.spans: list[tuple[str, float, float]] = []

    def start(self) -> None:
        self.t0 = time.perf_counter()

    def tick(self, boundary: bool = True) -> bool:
        """True once the window has closed; the first call that says so
        fixes its end.  A player whose steps differ in length passes
        ``boundary=False`` inside a round of its mix, so that every window
        holds whole rounds and its rate does not hang on which step the
        deadline falls in."""
        now = time.perf_counter()
        tr = self.tracer
        if tr is not None:
            if tr.t_begin is None:
                tr.begin()
            elif tr.active and now - tr.t_begin >= self.trace_seconds:
                tr.end()
        if boundary and self.t_end is None and \
                now - self.t0 >= self.seconds:
            self.t_end = now
            if tr is not None and tr.active:
                tr.end()
        return self.t_end is not None

    @property
    def length(self) -> float:
        return self.t_end - self.t0

    def span(self, name: str):
        """A benchmark-side host span (``perf_counter``) around a call."""
        return _Span(self.spans, name)


class _Span:
    __slots__ = ("log", "name", "t0")

    def __init__(self, log, name):
        self.log, self.name = log, name

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.log.append((self.name, self.t0, time.perf_counter() - self.t0))
        return False


# ---------------------------------------------------------------- a run
@dataclass
class Run:
    """What metric readers see."""

    cell: Cell
    setup_s: float
    window: Window
    player: object                      # the player's cell object
    peaks: dict
    chips: int
    reduction: object = None            # trace.Reduction of a traced run
    traced_steps: int = 0               # whole window steps in the trace
    obs_events: list = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return self.window.length


def _log(device: dict, msg: str) -> None:
    print(f"# [{device['kind']} x{device['count']}] {msg}", file=sys.stderr,
          flush=True)


def _stop_pool() -> None:
    """Stop the plan-build process pool the program started, and wait for
    its workers: a run leaves no process behind."""
    from repro.sim import pipeline

    pool = pipeline._PROCESS_POOL
    if pool is not None:
        pool.shutdown(wait=True)
        pipeline._reset_process_pool()


def stop_processes() -> None:
    """Stop every process a run started and wait for each to end: the
    plan-build pool and its workers, the forkserver that forks them, and
    the resource tracker beside it.  Both outlive a plain pool shutdown
    and the process itself, so each is told to stop and then reaped."""
    import gc
    from multiprocessing import forkserver, resource_tracker

    if "repro.sim.pipeline" in sys.modules:
        _stop_pool()
    gc.collect()       # the pool's queues give their semaphores back
    forkserver._forkserver._stop()
    resource_tracker._resource_tracker._stop()


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, bench_file: str = BENCHMARK_FILE,
        require_tpu: bool = True, rehearse: bool = False) -> dict:
    """One run; returns the result line as a dict."""
    cell = load_cell(workload, bench_file, rehearse=rehearse)
    devs = _device.devices(cell.chips, require_tpu=require_tpu)
    cell.chips = len(devs)
    dev = _device.describe(devs)
    peaks = _device.peaks(dev["kind"]) if require_tpu else {}

    from repro.obs import registry as obs
    from repro.sim import configure_xla_cache

    _log(dev, f"xla compilation cache: {configure_xla_cache()}")
    meter = _device.CompileMeter()
    player_mod = load_player(cell.traffic["player"])
    cellobj = player_mod.make(cell.config, cell.traffic, seed, cell.chips)
    cellobj.setup()
    setup_s = time.perf_counter() - t_start
    c_setup = meter.snapshot()
    _log(dev, f"setup_s={setup_s} " + " ".join(
        f"{k}={v}" for k, v in c_setup.items()))

    tracer = Tracer() if trace else None
    window = Window(seconds, tracer, cell.traffic.get("trace_seconds", 3.0))
    obs_on = trace and cell.traffic.get("obs_in_trace", False)
    if obs_on:
        obs.reset()
        obs.enable()
    try:
        window.start()
        cellobj.run_window(window)
    finally:
        if tracer is not None and tracer.active:
            tracer.end()
        if obs_on:
            obs.disable()
    c_win = meter.snapshot()
    _log(dev, f"window_s={window.length} in-window " + " ".join(
        f"{k}={c_win[k] - c_setup[k]}" for k in c_win))
    mem = _device.memory_peak_bytes(devs)

    r = Run(cell=cell, setup_s=setup_s, window=window,
            player=cellobj, peaks=peaks, chips=cell.chips)
    if obs_on:
        r.obs_events = [e for e in obs.wall_events()
                        if window.t0 <= e["ts"] <= window.t_end]
    if tracer is not None:
        tracer.read()
        lo, hi = tracer.window_ns()
        spans = list(window.spans) + [(e["name"], e["ts"], e["dur"])
                                      for e in r.obs_events]
        r.reduction = reduce(tracer.trace, lo, hi, cell.chips,
                             cellobj.module_patterns,
                             tracer.host_spans(spans))
        r.traced_steps = sum(
            1 for t0, t1 in getattr(cellobj, "step_times", ())
            if t0 >= tracer.t_begin and t1 <= tracer.t_end)

    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for name, m in cell.metrics.items():
        if m["kind"] != kind:
            continue
        value = load_metric(name)(r)
        if value is not None:
            metrics[name] = {"value": float(value), "unit": m["unit"]}

    cellobj.release()
    _stop_pool()
    checks = cellobj.checks(np.random.default_rng([seed, 0xC4EC]))
    limits = cell.traffic["limits"]
    correct = all(checks[k] <= limits[k] for k in checks)
    for k in checks:
        print(f"check {k} {checks[k]!r} limit {limits[k]!r} "
              f"[{dev['kind']} x{dev['count']}]", file=sys.stderr)
    sys.stderr.flush()

    device = dict(dev, memory_peak_bytes=mem)
    out = {"correct": bool(correct), "attempted": cellobj.attempted,
           "failed": cellobj.failed, "metrics": metrics, "device": device}
    if r.reduction is not None:
        device["busy_s"] = r.reduction.busy_s
        device["window_s"] = r.reduction.window_s
        out["breakdown"] = {"device_ops": r.reduction.device_ops,
                            "idle_gaps": r.reduction.idle_gaps}
    out["checks"] = {k: {"value": checks[k], "limit": limits[k]}
                     for k in checks}
    return out
