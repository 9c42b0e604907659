"""The device a run measures: the check that it is there, its published
peaks, its memory, and the compile counter."""
from __future__ import annotations

import json
import os
import threading

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


class NoChip(RuntimeError):
    """The run asks for an accelerator that JAX does not see."""


def devices(chips: int, require_tpu: bool = True):
    """The first ``chips`` devices; raises ``NoChip`` when JAX finds no
    TPU or fewer devices than the cell asks for.  With ``require_tpu``
    off (the CPU rehearsals) any backend will do, with as many of the
    devices as it has."""
    import jax

    devs = jax.devices()
    if not require_tpu:
        return devs[:chips]
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devs)}")
    return devs[:chips]


def describe(devs) -> dict:
    """``platform``, ``kind`` and ``count`` as JAX reports them."""
    import jax

    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices())}


def peaks(kind: str) -> dict:
    """Published peaks of one chip of ``kind``; a kind not in the table is
    an error, never a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"have {sorted(table)}")
    return table[kind]


def memory_peak_bytes(devs) -> int | None:
    """Peak bytes in use on the fullest device, where the backend says."""
    peak = None
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peak = max(peak or 0, int(stats["peak_bytes_in_use"]))
    return peak


class CompileMeter:
    """XLA compile seconds and counts, and persistent-cache hits/misses,
    summed from JAX's monitoring events (a cache hit still reports a
    backend compile event: the time it took to load the executable)."""

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

    def snapshot(self) -> dict:
        with self._lock:
            return {"compile_s": self.seconds, "compiles": self.compiles,
                    "cache_hits": self.hits, "cache_misses": self.misses}
