"""Chameleon tile DAGs with synthesized kernel times (arXiv:1711.06433 §6.1).

A copy of ``repro.core.workloads.chameleon`` and ``repro.sim.scenarios``'s
``with_ccr``: the same DAGs, the same seeded times and the same seeded
edge transfer costs, as plain numpy arrays.
"""
from __future__ import annotations

import zlib

import numpy as np

BLOCK_SIZES = (64, 128, 320, 512, 768, 960)
APPS = ("getrf", "posv", "potrf", "potri", "potrs")

# flops(b) per kernel class (dense tiles b×b)
_FLOPS = {
    "gemm": lambda b: 2.0 * b ** 3,
    "syrk": lambda b: 1.0 * b ** 3,
    "trsm": lambda b: 1.0 * b ** 3,
    "trmm": lambda b: 1.0 * b ** 3,
    "potrf": lambda b: b ** 3 / 3.0,
    "getrf": lambda b: 2.0 * b ** 3 / 3.0,
    "trtri": lambda b: b ** 3 / 3.0,
    "lauum": lambda b: b ** 3 / 3.0,
    "trsv": lambda b: 2.0 * b ** 2,
}

# CPU GFLOP/s per core; per accelerator type [peak GFLOP/s, half-efficiency
# block] per kernel class
_CPU_RATE = 15.0
_DEV = {
    1: {"gemm": (1000.0, 400.0), "syrk": (800.0, 400.0), "trsm": (250.0, 350.0),
        "trmm": (250.0, 350.0), "potrf": (60.0, 600.0), "getrf": (80.0, 600.0),
        "trtri": (60.0, 600.0), "lauum": (70.0, 600.0), "trsv": (5.0, 300.0)},
    2: {"gemm": (700.0, 300.0), "syrk": (560.0, 300.0), "trsm": (180.0, 280.0),
        "trmm": (180.0, 280.0), "potrf": (45.0, 500.0), "getrf": (60.0, 500.0),
        "trtri": (45.0, 500.0), "lauum": (50.0, 500.0), "trsv": (4.0, 250.0)},
}


def _times(names: list[str], block_size: int, num_types: int,
           seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    proc = np.zeros((len(names), num_types))
    for j, nm in enumerate(names):
        cls = nm.split("(")[0]
        fl = _FLOPS[cls](block_size)
        proc[j, 0] = fl / (_CPU_RATE * 1e9) * rng.lognormal(0.0, 0.08)
        for q in range(1, num_types):
            peak, b0 = _DEV[q][cls]
            eff = 1.0 / (1.0 + (b0 / block_size) ** 2)
            proc[j, q] = fl / (peak * 1e9 * eff) * rng.lognormal(0.0, 0.12)
    return proc * 1e3  # milliseconds


class _Builder:
    def __init__(self):
        self.names: list[str] = []
        self.edges: list[tuple[int, int]] = []

    def task(self, name: str, deps: list[int]) -> int:
        j = len(self.names)
        self.names.append(name)
        self.edges.extend((d, j) for d in deps if d is not None and d >= 0)
        return j


def _potrf_phase(b: _Builder, N: int, prefix: str,
                 entry: dict | None = None) -> dict:
    entry = entry or {}
    potrf: dict[int, int] = {}
    trsm: dict[tuple[int, int], int] = {}
    syrk_prev: dict[int, int] = {}
    gemm_prev: dict[tuple[int, int], int] = {}
    for kk in range(N):
        deps = [syrk_prev.get(kk, -1), entry.get(("diag", kk), -1)]
        potrf[kk] = b.task(f"{prefix}(%d)" % kk, deps)
        for i in range(kk + 1, N):
            deps = [potrf[kk], gemm_prev.get((i, kk), -1),
                    entry.get(("low", i, kk), -1)]
            trsm[(i, kk)] = b.task(f"trsm({i},{kk})", deps)
        for i in range(kk + 1, N):
            syrk_prev[i] = b.task(f"syrk({i},{kk})",
                                  [trsm[(i, kk)], syrk_prev.get(i, -1)])
            for jj in range(kk + 1, i):
                gemm_prev[(i, jj)] = b.task(
                    f"gemm({i},{jj},{kk})",
                    [trsm[(i, kk)], trsm[(jj, kk)],
                     gemm_prev.get((i, jj), -1)])
    out = {("diag", kk): potrf[kk] for kk in range(N)}
    out.update({("low", i, kk): t for (i, kk), t in trsm.items()})
    return out


def _potrs_phase(b: _Builder, N: int, lblocks: dict) -> None:
    upd: dict[int, int] = {}
    last_fwd: list[int] = []
    for kk in range(N):
        t = b.task(f"trsm(f{kk})", [upd.get(kk, -1),
                                    lblocks.get(("diag", kk), -1)])
        last_fwd.append(t)
        for i in range(kk + 1, N):
            upd[i] = b.task(f"gemm(f{i},{kk})",
                            [t, upd.get(i, -1),
                             lblocks.get(("low", i, kk), -1)])
    upd2: dict[int, int] = {}
    for kk in range(N - 1, -1, -1):
        deps = [upd2.get(kk, -1), lblocks.get(("diag", kk), -1), last_fwd[kk]]
        t = b.task(f"trsm(b{kk})", deps)
        for i in range(kk):
            upd2[i] = b.task(f"gemm(b{i},{kk})",
                             [t, upd2.get(i, -1),
                              lblocks.get(("low", kk, i), -1)])


def _getrf(b: _Builder, N: int) -> None:
    getrf: dict[int, int] = {}
    gemm_prev: dict[tuple[int, int], int] = {}
    for kk in range(N):
        getrf[kk] = b.task(f"getrf({kk})", [gemm_prev.get((kk, kk), -1)])
        trsm_u = {j: b.task(f"trsm(u{kk},{j})",
                            [getrf[kk], gemm_prev.get((kk, j), -1)])
                  for j in range(kk + 1, N)}
        trsm_l = {i: b.task(f"trsm(l{i},{kk})",
                            [getrf[kk], gemm_prev.get((i, kk), -1)])
                  for i in range(kk + 1, N)}
        for i in range(kk + 1, N):
            for j in range(kk + 1, N):
                gemm_prev[(i, j)] = b.task(
                    f"gemm({i},{j},{kk})",
                    [trsm_l[i], trsm_u[j], gemm_prev.get((i, j), -1)])


def chameleon(app: str, nb_blocks: int, block_size: int, num_types: int = 2,
              seed: int = 0):
    """``(names, edges (e, 2) int, proc (n, Q) ms)`` of one application."""
    if app not in APPS:
        raise ValueError(f"unknown app {app!r}")
    b = _Builder()
    N = nb_blocks
    if app == "potrf":
        _potrf_phase(b, N, "potrf")
    elif app == "potrs":
        _potrs_phase(b, N, {})
    elif app == "posv":
        _potrs_phase(b, N, _potrf_phase(b, N, "potrf"))
    elif app == "getrf":
        _getrf(b, N)
    elif app == "potri":
        lb = _potrf_phase(b, N, "potrf")
        tb = _potrf_phase(b, N, "trtri", entry=lb)
        _potrf_phase(b, N, "lauum", entry=tb)
    dseed = zlib.crc32(f"{app}|{nb_blocks}|{block_size}|{seed}".encode())
    proc = _times(b.names, block_size, num_types, seed=dseed)
    return b.names, np.asarray(b.edges, dtype=np.int64).reshape(-1, 2), proc


def ccr_comm(proc: np.ndarray, num_edges: int, ccr: float, seed: int,
             spread: float = 0.5) -> np.ndarray:
    """(e,) lognormal transfer costs with mean ``ccr`` × the mean best-type
    task time, from their own stream (``ccr == 0`` gives zeros)."""
    if ccr <= 0.0 or not num_edges:
        return np.zeros(num_edges)
    rng = np.random.default_rng([seed, 0xC077])
    base = float(np.min(proc, axis=1).mean())
    return ccr * base * rng.lognormal(-0.5 * spread ** 2, spread,
                                      size=num_edges)
