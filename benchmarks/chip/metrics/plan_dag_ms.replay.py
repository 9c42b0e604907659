"""Plan-DAG assembly per campaign call: the ``sim.pipeline.assemble``
spans (``BatchedPlanDag.from_plans``, padding and stacking of each
bucket), summed per call, mean over the window's calls, in milliseconds."""
from benchmarks.chip.spans import per_call_ms


def read(run):
    return per_call_ms(run, "sim.pipeline.assemble")
