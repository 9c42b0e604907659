"""Ahead-of-time compiles of the scheduler's device path for a TPU v5e.

Nothing here runs on a chip: each test lowers one jitted program of the
device path at the paper's §6 scale (Chameleon nb = 20 on 128 CPUs +
16 GPUs) and compiles it for a *described* ``v5e:2x2`` topology with the
TPU compiler that ships with jaxlib.  What the chip's compiler would refuse
(an unaligned Pallas tile, too much VMEM, a program that does not fit HBM)
fails here, at no chip time.  Inputs are ``ShapeDtypeStruct``s on one
described chip; the topology is described inside a module fixture, only
once a test of this file runs, and the persistent compilation cache is off
around the compiles (an entry written for a described chip cannot be read
back without one).
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.sim.scenarios import cholesky_scenario, netbound_scenario

HBM_BYTES = 16 * 1024 ** 3   # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                sharding=sharding)


def _specs_like(sharding, tree):
    return jax.tree_util.tree_map(
        lambda a: _spec(sharding, np.shape(a), a.dtype), tree)


def _fits_one_chip(compiled):
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, f"{used} bytes do not fit one v5e chip"


def _potrf20():
    return cholesky_scenario(nb_blocks=20, block_size=512, counts=(128, 16),
                             ccr=0.5)


def test_bucket_makespans_compiles_at_potrf_nb20(one_chip):
    from repro.sim.batch import BatchedPlanDag, _bucket_makespans, \
        search_envelope

    sc = _potrf20()
    n_pad, p_pad = search_envelope(sc.graph, sc.machine)
    assert (n_pad, p_pad) == (2048, 4)
    B, S = 4, 33          # four adapters' plans, clean row + 32 seeds
    f = jnp.float32       # the evaluator runs in JAX's default 32-bit mode
    bd = BatchedPlanDag(
        order=_spec(one_chip, (B, n_pad), jnp.int32),
        pred=_spec(one_chip, (B, n_pad, p_pad), jnp.int32),
        pred_mask=_spec(one_chip, (B, n_pad, p_pad), jnp.bool_),
        pred_delay=_spec(one_chip, (B, n_pad, p_pad), f),
        floor=_spec(one_chip, (B, n_pad), f))
    times = _spec(one_chip, (B, S, n_pad), f)
    compiled = _bucket_makespans.lower(bd, times).compile()
    _fits_one_chip(compiled)


def test_contended_durations_compiles_under_x64(one_chip):
    from repro.sim import make_network, make_scheduler
    from repro.sim.batch import ContendedBucket, _bucket_key, \
        _contended_durations, _pow2
    from repro.sim.network import CONTENTION_ITERS, plan_transfers

    net = make_network("maxmin_fair")
    envs = set()
    for seed in range(300, 306):
        sc = netbound_scenario(seed=seed)
        plan = make_scheduler("hlp_ols").allocate(sc.graph, sc.machine)
        tr = plan_transfers(sc.graph, plan, net)
        envs.add((*_bucket_key(sc.graph, plan), _pow2(tr.count),
                  tr.num_links))
    n_pad, p_pad, t_pad, links = max(envs)
    B = 12                # six seeds x two allocations
    with jax.enable_x64(True):
        f = jnp.float64
        cb = ContendedBucket(
            order=_spec(one_chip, (B, n_pad), jnp.int32),
            pred=_spec(one_chip, (B, n_pad, p_pad), jnp.int32),
            pred_mask=_spec(one_chip, (B, n_pad, p_pad), jnp.bool_),
            pred_tid=_spec(one_chip, (B, n_pad, p_pad), jnp.int32),
            times=_spec(one_chip, (B, n_pad), f),
            src=_spec(one_chip, (B, t_pad), jnp.int32),
            size=_spec(one_chip, (B, t_pad), f),
            up=_spec(one_chip, (B, t_pad), jnp.int32),
            dn=_spec(one_chip, (B, t_pad), jnp.int32),
            t_mask=_spec(one_chip, (B, t_pad), jnp.bool_),
            capacity=_spec(one_chip, (B,), f))
        compiled = _contended_durations.lower(
            cb, links, CONTENTION_ITERS).compile()
    _fits_one_chip(compiled)


def test_solve_choice_compiles_at_potrf_nb20(one_chip):
    from repro.core.allocation import AllocationProblem
    from repro.core.hlp_jax import PaddedDag, _solve_choice

    sc = _potrf20()
    prob = AllocationProblem.build(sc.graph, sc.counts, comm_aware=True,
                                   rigid=True)
    d = _specs_like(one_chip, PaddedDag.from_graph(sc.graph))
    n, C = prob.p_choice.shape
    f = jnp.float32
    compiled = _solve_choice.lower(
        d, _spec(one_chip, (n, C), f), _spec(one_chip, (n, C), f),
        _spec(one_chip, prob.type_mask.shape, f),
        _spec(one_chip, (len(prob.counts),), f), 400,
        _spec(one_chip, (), jnp.int32), use_comm=True).compile()
    _fits_one_chip(compiled)


def test_maxplus_matmul_compiles_at_2048(one_chip):
    from repro.kernels.maxplus.maxplus import maxplus_matmul

    a = _spec(one_chip, (2048, 2048), jnp.float32)
    compiled = maxplus_matmul.lower(a, a, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits_one_chip(compiled)


def test_flash_attention_compiles_at_2048_bf16(one_chip):
    from repro.kernels.flash_attention.flash_attention import \
        flash_attention_bhsd

    q = _spec(one_chip, (16, 2048, 128), jnp.bfloat16)
    compiled = flash_attention_bhsd.lower(q, q, q, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits_one_chip(compiled)
