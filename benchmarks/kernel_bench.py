"""Microbenchmarks for the Pallas kernels (compiled on TPU, interpreted
elsewhere)."""
from __future__ import annotations


def run(full: bool) -> list[str]:
    try:
        from .kernel_bench_impl import run_impl
    except ImportError:
        print("# kernels: kernel benchmarks not yet available")
        return []
    return run_impl(full)
