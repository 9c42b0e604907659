"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode on
the CPU backend, by the wrappers' own backend rule)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.maxplus import ops as mops
from repro.kernels.maxplus.maxplus import maxplus_matmul
from repro.kernels.maxplus.ref import maxplus_matmul_ref


def test_interpret_mode_only_off_tpu(monkeypatch):
    """One rule in every wrapper: interpret on the CPU backend, compile on a
    TPU backend, and refuse an explicit interpret request there."""
    from repro import kernels
    assert jax.default_backend() == "cpu"
    assert kernels.resolve_interpret(None) is True
    assert kernels.resolve_interpret(False) is False
    monkeypatch.setattr(kernels.jax, "default_backend", lambda: "tpu")
    assert kernels.resolve_interpret(None) is False
    assert kernels.resolve_interpret(False) is False
    with pytest.raises(ValueError, match="interpret mode on a TPU"):
        kernels.resolve_interpret(True)


@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 128, 384),
                                   (128, 256, 128), (512, 512, 256)])
@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_maxplus_matches_ref(m, k, n, dtype):
    rng = np.random.default_rng(m + k + n)
    a = jnp.asarray(rng.normal(size=(m, k)).astype(dtype))
    b = jnp.asarray(rng.normal(size=(k, n)).astype(dtype))
    out = maxplus_matmul(a, b)
    ref = maxplus_matmul_ref(a, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("bm,bn,bk", [(64, 64, 64), (128, 128, 64)])
def test_maxplus_block_shapes(bm, bn, bk):
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=(256, 128)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(128, 256)).astype(np.float32))
    out = maxplus_matmul(a, b, bm=bm, bn=bn, bk=bk)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(maxplus_matmul_ref(a, b)), atol=1e-5)


def test_closure_matches_taskgraph_critical_path():
    from repro.core.workloads import chameleon
    for app, nb in (("potrf", 5), ("potrs", 10)):
        g = chameleon(app, nb, 320)
        adj = mops.dense_adjacency(g.n, g.edges, pad_to=128)
        times = np.zeros(adj.shape[0], np.float32)
        times[:g.n] = g.proc[:, 0]
        fin = mops.longest_path_closure(jnp.asarray(adj), jnp.asarray(times))
        assert float(jnp.max(fin[:g.n])) == pytest.approx(
            g.critical_path(g.proc[:, 0]), rel=1e-5)


def test_batched_ranks():
    from repro.core.workloads import chameleon
    g = chameleon("potrf", 5, 320)
    adj = mops.dense_adjacency(g.n, g.edges, pad_to=64)
    times = np.zeros((2, adj.shape[0]), np.float32)
    times[0, :g.n] = g.proc[:, 0]
    times[1, :g.n] = g.proc[:, 1]
    ranks = mops.batched_ranks(jnp.asarray(np.stack([adj, adj])),
                               jnp.asarray(times))
    for q in range(2):
        expect = g.upward_rank(g.proc[:, q])
        np.testing.assert_allclose(np.asarray(ranks[q, :g.n]), expect,
                                   rtol=1e-5)


@pytest.mark.parametrize("s,h,hkv,d", [(256, 4, 4, 64), (512, 4, 2, 64),
                                       (256, 8, 1, 128), (384, 6, 2, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(s, h, hkv, d, dtype, causal):
    rng = np.random.default_rng(s + h)
    q = jnp.asarray(rng.normal(size=(2, s, h, d)), dtype)
    k = jnp.asarray(rng.normal(size=(2, s, hkv, d)), dtype)
    v = jnp.asarray(rng.normal(size=(2, s, hkv, d)), dtype)
    out = flash_attention(q, k, v, causal=causal)
    g = h // hkv
    kb, vb = jnp.repeat(k, g, 2), jnp.repeat(v, g, 2)
    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(2 * h, s, d)
    ref = attention_ref(fold(q), fold(kb), fold(vb), causal=causal)
    ref = ref.reshape(2, h, s, d).transpose(0, 2, 1, 3)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


def test_flash_matches_model_attention_path():
    """use_pallas=True model attention equals the einsum path."""
    from repro.configs import get_smoke_config
    from repro.models import layers as L
    cfg = get_smoke_config("granite-3-2b")
    cfg = type(cfg)(**{**cfg.__dict__, "dtype": "float32",
                       "param_dtype": "float32"})
    p = L.attn_init(cfg, jax.random.PRNGKey(0))
    x = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (2, 128, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(128), (2, 128))
    y_ref = L.attn_apply(cfg, p, x, pos, causal=True)
    cfg2 = type(cfg)(**{**cfg.__dict__, "use_pallas": True})
    y_pal = L.attn_apply(cfg2, p, x, pos, causal=True)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               atol=1e-4, rtol=1e-4)
