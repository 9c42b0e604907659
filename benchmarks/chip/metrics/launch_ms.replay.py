"""Bucket launch per campaign call: the ``sim.pipeline.launch`` spans (the
times grid to the device and the async call of each bucket's program),
summed per call, mean over the window's calls, in milliseconds."""
from benchmarks.chip.spans import per_call_ms


def read(run):
    return per_call_ms(run, "sim.pipeline.launch")
