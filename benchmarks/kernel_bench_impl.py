"""Kernel microbenchmarks.  The Pallas kernels compile on a TPU backend and
run in interpret mode elsewhere (``repro.kernels.resolve_interpret``); the
summary line says which mode ran, since an interpreted time says nothing
about the device."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np


def _time(fn, *args, reps=3):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps


def run_impl(full: bool) -> list[str]:
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.maxplus.maxplus import maxplus_matmul
    from repro.kernels.maxplus.ref import maxplus_matmul_ref

    lines = []
    rng = np.random.default_rng(0)
    sizes = [(256, 256, 256)] + ([(512, 512, 512)] if full else [])
    for (m, k, n) in sizes:
        a = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
        b = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32))
        t_pal = _time(lambda x, y: maxplus_matmul(x, y), a, b)
        t_ref = _time(lambda x, y: maxplus_matmul_ref(x, y), a, b)
        err = float(jnp.abs(maxplus_matmul(a, b) - maxplus_matmul_ref(a, b)).max())
        lines.append(f"kernels/maxplus_{m}x{k}x{n},{t_pal*1e6:.0f},"
                     f"ref_us={t_ref*1e6:.0f};max_err={err:.1e}")

    s, h, d = (512, 4, 64) if not full else (1024, 8, 64)
    q = jnp.asarray(rng.normal(size=(2, s, h, d)).astype(np.float32))
    kk = jnp.asarray(rng.normal(size=(2, s, h, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(2, s, h, d)).astype(np.float32))
    t_pal = _time(lambda *x: flash_attention(*x, causal=True), q, kk, v)
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(2 * h, s, d)
    t_ref = _time(lambda *x: attention_ref(*x, causal=True),
                  fold(q), fold(kk), fold(v))
    lines.append(f"kernels/flash_attn_s{s},{t_pal*1e6:.0f},"
                 f"ref_us={t_ref*1e6:.0f}")
    from repro.kernels import resolve_interpret
    mode = "interpret" if resolve_interpret(None) else "compiled"
    print(f"# kernels: {len(lines)} benchmarks ({mode} mode on "
          f"{jax.default_backend()})")
    return lines
