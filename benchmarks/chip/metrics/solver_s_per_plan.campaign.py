"""Plan-build seconds per plan: ``PipelineStats.plan_build_s`` (solver
seconds summed over the pool's workers) over the plans the window built."""


def read(run):
    d = run.player
    return sum(d.plan_build_s) / d.attempted if d.attempted else None
