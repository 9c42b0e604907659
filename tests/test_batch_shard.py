"""Mesh-sharded bucket execution: padding round trips and mesh parity.

The plan axis of each bucket shards over the explicit 1-D campaign mesh
(``shard_map``), padded to a shard-divisible count first
(``_pad_plan_axis``) so no divides-evenly assumption survives — the
regression tests pin a *prime* plan count.  Multi-device behavior is
exercised in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count`` (the parent process
pins the single-device CPU topology at jax import).
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.sim import make_scheduler, set_campaign_mesh
from repro.sim.batch import (BatchedPlanDag, _pad_plan_axis,
                             sample_actual_batch)
from repro.sim.engine import NoiseModel
from repro.sim.scenarios import default_suite


def _items(count):
    suite = default_suite(seed=3)
    noise = NoiseModel("lognormal", 0.1)
    items, times = [], []
    for sc in suite:
        for name in ("heft", "hlp_ols"):
            plan = make_scheduler(name).allocate(sc.graph, sc.machine)
            items.append((sc.graph, plan))
            times.append(sample_actual_batch(sc.graph, plan, noise, [0, 1]))
            if len(items) == count:
                return items, times
    raise AssertionError(f"suite too small for {count} items")


@pytest.mark.parametrize("B,multiple", [(7, 4), (5, 3), (4, 4), (1, 8)])
def test_pad_plan_axis_round_trip(B, multiple):
    import jax.numpy as jnp
    items, times = _items(B)
    bd = BatchedPlanDag.from_plans(items, pad_to=(64, 8))
    tt = jnp.asarray(np.stack([np.pad(t, ((0, 0), (0, 64 - t.shape[1])))
                               for t in times]))
    bdp, tp, B_out = _pad_plan_axis(bd, tt, multiple)
    assert B_out == B
    want = B + (-B) % multiple
    assert bdp.order.shape[0] == want and tp.shape[0] == want
    assert tp.shape[0] % multiple == 0
    # padded lanes repeat item 0, so the padded bucket stays evaluable
    np.testing.assert_array_equal(np.asarray(bdp.order[B:]),
                                  np.tile(np.asarray(bd.order[:1]),
                                          (want - B, 1)))


def test_set_campaign_mesh_validates_axis_name():
    import jax
    from jax.sharding import Mesh
    with pytest.raises(ValueError, match="plans"):
        set_campaign_mesh(Mesh(np.asarray(jax.devices()), ("batch",)))
    set_campaign_mesh(None)   # reset the default


_SUBPROCESS_PARITY = textwrap.dedent("""
    import numpy as np
    import jax
    from jax.sharding import Mesh
    assert jax.local_device_count() == 4, jax.local_device_count()

    from repro.sim import make_scheduler, set_campaign_mesh
    from repro.sim.batch import (_bucket_key, bucketed_makespans,
                                 campaign_mesh, fixed_envelope_makespans,
                                 sample_actual_batch)
    from repro.sim.engine import NoiseModel
    from repro.sim.scenarios import default_suite

    noise = NoiseModel("lognormal", 0.1)
    items, times = [], []
    for sc in default_suite(seed=3):
        for name in ("heft", "hlp_ols"):
            plan = make_scheduler(name).allocate(sc.graph, sc.machine)
            items.append((sc.graph, plan))
            times.append(sample_actual_batch(sc.graph, plan, noise, [0, 1, 2]))
    items, times = items[:7], times[:7]   # prime plan count: 7 % 4 != 0
    pad_to = tuple(np.max([_bucket_key(g, p) for g, p in items], axis=0))

    def run():
        # per-key buckets, then all seven plans in one envelope bucket
        return (bucketed_makespans(items, times)
                + fixed_envelope_makespans(items, times, pad_to))

    assert campaign_mesh().devices.size == 4
    shard = run()
    set_campaign_mesh(Mesh(np.asarray(jax.devices()[:1]), ("plans",)))
    try:
        single = run()
    finally:
        set_campaign_mesh(None)
    for a, b in zip(shard, single):
        assert np.array_equal(a, b), "shard_map != one-device mesh"
    print("PARITY_OK")
""")


def test_shard_map_reproduces_pmap_across_four_devices():
    """The shard_map program over a four-device campaign mesh equals the
    one-device mesh (set through ``set_campaign_mesh``) bit-for-bit, at a
    prime plan count on a forced 4-device CPU topology (subprocess: the
    device count is fixed at jax import)."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH="src")
    proc = subprocess.run([sys.executable, "-c", _SUBPROCESS_PARITY],
                          capture_output=True, text=True, env=env,
                          cwd=os.path.join(os.path.dirname(__file__), ".."),
                          timeout=300)
    assert proc.returncode == 0, proc.stderr
    assert "PARITY_OK" in proc.stdout
