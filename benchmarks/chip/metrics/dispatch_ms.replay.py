"""Host input preparation per campaign call: ``PipelineStats.dispatch_s``
(noise sampling, plan-DAG assembly and async dispatch), mean over the
window's calls, in milliseconds."""


def read(run):
    d = run.player.dispatch_s
    return 1e3 * sum(d) / len(d) if d else None
