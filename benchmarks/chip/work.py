"""The work a replay call asks of the device, from the unpadded plans.

A plan of n tasks replays over the augmented DAG: its E edges are the DAG's
edges plus one per consecutive pair on a processor.  Per noise row each
edge costs an add (finish + delay) and a max, and each task an add (start
+ time): 2·E + n operations.  What must move: the realized times read
(n floats per row), the makespan written (one float per row), and the plan
structure read once per call (an index and a delay per edge, an order
index per task).  Padding to a bucket envelope adds neither, so the count
is the same whatever implements the replay.
"""
from __future__ import annotations

from dataclasses import dataclass

F32 = 4


@dataclass(frozen=True)
class Work:
    ops: float
    bytes: float


def replay_work(plans, rows: int) -> Work:
    """``plans``: ``(n, dag_edges, sequences)`` per plan; ``rows``: noise
    rows replayed per plan in one call."""
    ops = moved = 0.0
    for n, dag_edges, sequences in plans:
        e = dag_edges + sum(max(len(s) - 1, 0) for s in sequences)
        ops += rows * (2 * e + n)
        moved += rows * n * F32 + rows * F32 + e * 2 * F32 + n * F32
    return Work(ops=ops, bytes=moved)


def roofline_s(work: Work, peaks: dict, chips: int) -> tuple[float, str]:
    """The least time ``chips`` chips could take, and which bound sets
    it."""
    t_ops = work.ops / (peaks["flops_per_s"] * chips)
    t_bytes = work.bytes / (peaks["hbm_bytes_per_s"] * chips)
    return (t_ops, "ops") if t_ops >= t_bytes else (t_bytes, "bytes")
