"""Noise sampling per campaign call: the ``sim.pipeline.sample`` spans
(each plan's noise grid and its float64 conversion), summed per call,
mean over the window's calls, in milliseconds."""
from benchmarks.chip.spans import per_call_ms


def read(run):
    return per_call_ms(run, "sim.pipeline.sample")
