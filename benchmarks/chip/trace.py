"""Device traces: taking one, and reducing it to numbers.

A traced run records a ``jax.profiler`` trace over whole window steps.  The
reduction works on plain intervals, so it reads the same whatever made
them:

* device operations are the events of each device plane's ``XLA Ops``
  line (``/device:TPU:<n>``); on the CPU backend, where there is no device
  plane, the host events that carry an ``hlo_op`` and an ``hlo_module``
  stat (that is what the tests record);
* an XLA module's device time is the union of its ``XLA Modules`` events,
  or of its operations' intervals where that line is missing;
* busy time is the union of a device's operation intervals inside the
  traced window, and the idle share is 1 − busy / window;
* host spans (the benchmark's own and, where enabled, the program's
  ``repro.obs`` spans, both on ``time.perf_counter``) are put on the
  trace's clock through one anchor annotation, and each instant of an
  idle gap of the device is charged to the innermost host span then open.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
import tempfile
import time
import warnings
from dataclasses import dataclass, field

ANCHOR = "chipbench.anchor"
_DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:(\d+)$")
_MODULE_SUFFIX = re.compile(r"\(\d+\)$")


@dataclass
class Trace:
    """Intervals in nanoseconds on the trace's clock."""

    ops: dict[int, list[tuple[str, float, float]]] = field(
        default_factory=dict)                  # device -> (op, start, end)
    modules: dict[int, list[tuple[str, float, float]]] = field(
        default_factory=dict)                  # device -> (module, s, e)
    annotations: list[tuple[str, float, float]] = field(
        default_factory=list)                  # host TraceAnnotations


def module_name(name: str) -> str:
    """``jit__bucket_makespans(12)`` -> ``jit__bucket_makespans``."""
    return _MODULE_SUFFIX.sub("", name)


def op_name(text: str) -> str:
    """``%while.98 = (s32[] ...) while(...)`` -> ``while.98``: TPU op events
    are named by their whole HLO instruction."""
    head = text.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def _device_line(tr: Trace, dev: int, line) -> None:
    if line.name == "XLA Modules":
        tr.modules.setdefault(dev, []).extend(
            (module_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events)
    elif line.name == "XLA Ops":
        # ops inside a loop are recorded per iteration, nested in the loop's
        # own event: keep the outermost events only (their union is the
        # same), and read no name of a nested one
        ops = tr.ops.setdefault(dev, [])
        top_end = float("-inf")
        for e in line.events:
            s = e.start_ns
            if s < top_end:
                continue
            top_end = s + e.duration_ns
            ops.append((op_name(e.name), s, top_end))


def read_profile(pd) -> Trace:
    """Pull operations, modules and host annotations out of a
    ``jax.profiler.ProfileData``."""
    tr = Trace()
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                _device_line(tr, int(m.group(1)), line)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == ANCHOR or line.name.startswith("python"):
                    tr.annotations.append(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns))
                    continue
                if not e.name or e.name.startswith(("end: ",
                                                    "ThreadpoolListener")):
                    continue
                with warnings.catch_warnings():   # jaxlib's stats type
                    warnings.simplefilter("ignore", DeprecationWarning)
                    stats = dict(e.stats)
                if "hlo_op" in stats and "hlo_module" in stats:
                    dev = int(stats.get("device_ordinal", 0))
                    end = e.start_ns + e.duration_ns
                    tr.ops.setdefault(dev, []).append((e.name, e.start_ns,
                                                       end))
                    tr.modules.setdefault(-1 - dev, []).append(
                        (str(stats["hlo_module"]), e.start_ns, end))
    # CPU op events carry their module: fold those into per-device modules
    # only where no device plane gave a module line
    for key in [k for k in tr.modules if k < 0]:
        dev = -1 - key
        if dev not in tr.modules:
            tr.modules[dev] = tr.modules[key]
        del tr.modules[key]
    return tr


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged (start, end) intervals clipped to [lo, hi]."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``intervals`` inside [lo, hi]."""
    return sum(e - s for s, e in union(intervals, lo, hi))


def busy_ns(tr: Trace, lo: float, hi: float) -> dict[int, float]:
    """Per device, the time inside [lo, hi] in which an operation ran."""
    return {dev: covered([(s, e) for _, s, e in ops], lo, hi)
            for dev, ops in tr.ops.items()}


def module_ns(tr: Trace, pattern: str, lo: float,
              hi: float) -> dict[int, float]:
    """Per device, the time inside [lo, hi] of the XLA modules whose name
    contains ``pattern``."""
    return {dev: covered([(s, e) for name, s, e in mods if pattern in name],
                         lo, hi)
            for dev, mods in tr.modules.items()}


def op_ns(tr: Trace, lo: float, hi: float) -> dict[str, float]:
    """Device time per ``<module>/<operation>`` inside [lo, hi], averaged
    over the devices that ran anything."""
    totals: dict[str, float] = {}
    for dev, ops in tr.ops.items():
        mods = sorted(tr.modules.get(dev, []), key=lambda m: m[1])
        starts = [m[1] for m in mods]
        for name, s, e in ops:
            d = min(e, hi) - max(s, lo)
            if d <= 0:
                continue
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and mods[i][2] >= e and "/" not in name:
                name = f"{mods[i][0]}/{name}"
            totals[name] = totals.get(name, 0.0) + d
    ndev = max(1, len(tr.ops))
    return {k: v / ndev for k, v in totals.items()}


def gaps(busy: list[tuple[float, float]], lo: float,
         hi: float) -> list[tuple[float, float]]:
    """The idle intervals of [lo, hi] between merged busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def charge_gaps(idle: list[tuple[float, float]],
                spans: list[tuple[str, float, float]]) -> dict[str, float]:
    """Idle time per host activity: each instant of a gap goes to the
    innermost (shortest) host span that covers it, or to ``"none"``."""
    spans = sorted(spans, key=lambda x: x[1])
    starts = [x[1] for x in spans]
    longest = max((e - s for _, s, e in spans), default=0.0)
    out: dict[str, float] = {}
    for gs, ge in idle:
        lo = bisect.bisect_left(starts, gs - longest)
        hi = bisect.bisect_right(starts, ge)
        cover = [(n, max(s, gs), min(e, ge), e - s)
                 for n, s, e in spans[lo:hi] if min(e, ge) > max(s, gs)]
        cuts = sorted({gs, ge, *(c[1] for c in cover), *(c[2] for c in cover)})
        for a, b in zip(cuts[:-1], cuts[1:]):
            inside = [c for c in cover if c[1] <= a and c[2] >= b]
            name = min(inside, key=lambda c: c[3])[0] if inside else "none"
            out[name] = out.get(name, 0.0) + (b - a)
    return out


@dataclass
class Reduction:
    """What one traced window reduces to."""

    window_s: float
    busy_s: float                       # mean over the chips used
    idle_pct: float
    module_s: dict[str, float]          # per module pattern, mean over chips
    device_ops: list[list]               # [[op, seconds]], the ten largest
    idle_gaps: list[list]                # [[host activity, seconds]]
    devices: int


def reduce(tr: Trace, lo: float, hi: float, chips: int,
           module_patterns=(), host_spans=()) -> Reduction:
    """Reduce the window [lo, hi] (ns) of a trace on ``chips`` devices."""
    devs = sorted(tr.ops)[:chips] or list(range(chips))
    busy = busy_ns(tr, lo, hi)
    busy_mean = sum(busy.get(d, 0.0) for d in devs) / len(devs)
    window = hi - lo
    mods = {}
    for pat in module_patterns:
        per = module_ns(tr, pat, lo, hi)
        mods[pat] = sum(per.get(d, 0.0) for d in devs) / len(devs) / 1e9
    charged: dict[str, float] = {}
    for d in devs:
        merged = union([(s, e) for _, s, e in tr.ops.get(d, [])], lo, hi)
        for k, v in charge_gaps(gaps(merged, lo, hi),
                                list(host_spans)).items():
            charged[k] = charged.get(k, 0.0) + v / len(devs)
    ops = sorted(op_ns(tr, lo, hi).items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(charged.items(), key=lambda kv: -kv[1])[:10]
    return Reduction(
        window_s=window / 1e9, busy_s=busy_mean / 1e9,
        idle_pct=100.0 * (1.0 - busy_mean / window) if window > 0 else 0.0,
        module_s=mods, device_ops=[[k, v / 1e9] for k, v in ops],
        idle_gaps=[[k, v / 1e9] for k, v in idle], devices=len(devs))


class Tracer:
    """Takes one profiler trace over whole window steps.

    ``begin()`` starts it and stamps the anchor; ``end()`` stops it, reads
    it back and deletes the files.  Host spans recorded on
    ``time.perf_counter`` map onto the trace by ``to_ns``."""

    def __init__(self):
        self.dir = None
        self.t_begin = self.t_end = None
        self.trace: Trace | None = None
        self._offset_ns = None

    def begin(self) -> None:
        import jax

        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # no per-call Python events: they
        opts.enable_hlo_proto = False     # slow the host being measured
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation(ANCHOR):
            self.t_begin = time.perf_counter()

    def end(self) -> None:
        """Stop tracing (at a step boundary inside the window)."""
        import jax

        self.t_end = time.perf_counter()
        jax.profiler.stop_trace()

    def read(self) -> None:
        """Read the trace back and delete its files (after the window)."""
        from jax.profiler import ProfileData

        try:
            files = glob.glob(os.path.join(self.dir, "plugins", "profile",
                                           "*", "*.xplane.pb"))
            if not files:
                raise RuntimeError("the profiler wrote no trace")
            self.trace = read_profile(ProfileData.from_file(files[0]))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        anchors = [s for name, s, _ in self.trace.annotations
                   if name == ANCHOR]
        if not anchors:
            raise RuntimeError("the trace has no anchor annotation")
        self._offset_ns = anchors[0] - self.t_begin * 1e9

    @property
    def active(self) -> bool:
        return self.t_begin is not None and self.t_end is None

    def to_ns(self, t: float) -> float:
        return t * 1e9 + self._offset_ns

    def window_ns(self) -> tuple[float, float]:
        return self.to_ns(self.t_begin), self.to_ns(self.t_end)

    def host_spans(self, spans) -> list[tuple[str, float, float]]:
        """``(name, t0, dur)`` spans in seconds -> intervals in ns."""
        return [(name, self.to_ns(t0), self.to_ns(t0 + dur))
                for name, t0, dur in spans]
