"""General players, one per kind of traffic; a traffic file names its
player.  Each module has ``make(config, traffic, seed, chips)``, which
returns an object with ``setup()``, ``run_window(window)``, ``release()``,
``checks(rng, control=False)``, ``attempted``, ``failed`` and
``module_patterns`` (the XLA modules whose device time a traced run
reads); a player whose steps are whole units of work also keeps
``step_times``.
"""
from __future__ import annotations

import time

import numpy as np

from ..gen.chameleon import ccr_comm, chameleon


def graph_inputs(app: str, nb: int, block: int, ccr: float, seed: int):
    """``(names, edges, proc, comm)`` of one Chameleon DAG."""
    names, edges, proc = chameleon(app, nb, block, seed=seed)
    return names, edges, proc, ccr_comm(proc, len(edges), ccr, seed)


def task_graph(inputs, templates: dict | None = None):
    """The program's ``TaskGraph`` of generated inputs.

    ``TaskGraph.build`` derives the graph's index arrays and order from its
    edges in Python, which takes a tenth of a second at a thousand tasks.
    Graphs of one application and size share all of that, so with a
    ``templates`` dict the first graph of each shape is built and the rest
    are copies of it with their own times and transfer costs."""
    import dataclasses

    from repro.core.dag import TaskGraph

    names, edges, proc, comm = inputs
    key = (tuple(names), proc.shape[1])
    if templates is not None and key in templates:
        return dataclasses.replace(templates[key], proc=proc, comm=comm)
    g = TaskGraph.build(proc, edges, names=names, comm=comm)
    if templates is not None:
        templates[key] = g
    return g


def derive(seed: int, *keys) -> int:
    """A 31-bit seed drawn from the run's seed and ``keys``."""
    return int(np.random.default_rng([seed, *keys]).integers(2 ** 31 - 1))


def control_dtype():
    """bfloat16: the precision below the float32 the evaluator computes
    in — the control's."""
    import ml_dtypes

    return ml_dtypes.bfloat16


def sweep(entries, seeds, scale: float, spans: list | None = None):
    """One ``pipelined_sweep_makespans`` call: per plan a clean row, then
    one lognormal(``scale``) row per seed, drawn by the program's
    ``sample_actual_batch``.  Returns the makespans and the call's
    ``PipelineStats``; each noise draw is logged to ``spans``."""
    from repro.sim import NoiseModel
    from repro.sim.batch import sample_actual_batch
    from repro.sim.pipeline import last_pipeline_stats, \
        pipelined_sweep_makespans

    clean, noise = NoiseModel(), NoiseModel("lognormal", scale)

    def sample(g, plan):
        t0 = time.perf_counter()
        out = np.vstack([sample_actual_batch(g, plan, clean, [0]),
                         sample_actual_batch(g, plan, noise, seeds)])
        if spans is not None:
            spans.append(("bench.noise_sample", t0, time.perf_counter() - t0))
        return out

    out = pipelined_sweep_makespans(entries, sample_fn=sample)
    return out, last_pipeline_stats()
