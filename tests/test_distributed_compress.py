"""Tests for distributed/compress.py: error-feedback int8 gradient
compression and the explicit ppermute ring all-reduce.

The EF tests are pure single-device numerics. The ring tests run under
``shard_map`` over however many devices the process exposes — they skip
below 2 devices; the CI ``tier1-sharded`` job runs them on the 8-way
forced host platform (``XLA_FLAGS=--xla_force_host_platform_device_count=8``).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed.compress import (
    compressed_allreduce,
    ef_compress_leaf,
    ef_int8_transform,
    init_ef_state,
    ring_allreduce,
)

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="ring all-reduce needs >= 2 devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")


# ---------------------------------------------------------------------------
# error-feedback int8
# ---------------------------------------------------------------------------

class TestEfInt8:
    def test_single_step_error_bounded(self):
        g = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
        deq, err = ef_compress_leaf(g, jnp.zeros_like(g))
        # int8 symmetric quant: per-element error <= scale/2 = amax/254
        amax = float(jnp.max(jnp.abs(g)))
        assert float(jnp.max(jnp.abs(deq - g))) <= amax / 254 + 1e-6
        np.testing.assert_allclose(np.asarray(g - deq), np.asarray(err),
                                   atol=1e-6)

    def test_error_feedback_accumulates(self):
        """Sum of compressed gradients tracks the sum of true gradients:
        the EF residual is carried, not dropped (Seide et al.)."""
        key = jax.random.PRNGKey(1)
        e = jnp.zeros((128,), jnp.float32)
        total_true = jnp.zeros((128,))
        total_sent = jnp.zeros((128,))
        for i in range(20):
            key, sub = jax.random.split(key)
            g = jax.random.normal(sub, (128,))
            deq, e = ef_compress_leaf(g, e)
            total_true += g
            total_sent += deq
        # residual bounds the drift: |sum_true - sum_sent| == |e|, which
        # is at most one quantization step of the *last* compressed value
        drift = jnp.max(jnp.abs(total_true - total_sent))
        assert float(drift) == pytest.approx(float(jnp.max(jnp.abs(e))),
                                             abs=1e-5)
        assert float(drift) < 0.1

    def test_tree_transform_and_none_leaves(self):
        grads = {"a": jax.random.normal(jax.random.PRNGKey(0), (8, 8)),
                 "b": {"c": jnp.ones((4,)), "d": None}}
        ef = init_ef_state(grads)
        assert ef["b"]["d"] is None
        out_g, out_e = ef_int8_transform(grads, ef)
        assert out_g["b"]["d"] is None and out_e["b"]["d"] is None
        np.testing.assert_allclose(np.asarray(out_g["a"]),
                                   np.asarray(grads["a"]), atol=0.03)
        # second application with the carried error reduces the bias
        g2, e2 = ef_int8_transform(grads, out_e)
        two_step = np.asarray(out_g["a"] + g2["a"])
        np.testing.assert_allclose(two_step, np.asarray(2 * grads["a"]),
                                   atol=0.03)

    def test_zero_gradient_stable(self):
        g = jnp.zeros((16,))
        deq, err = ef_compress_leaf(g, jnp.zeros_like(g))
        assert float(jnp.max(jnp.abs(deq))) == 0.0
        assert float(jnp.max(jnp.abs(err))) == 0.0


# ---------------------------------------------------------------------------
# ring all-reduce
# ---------------------------------------------------------------------------

def _ring_mesh():
    n = len(jax.devices())
    return jax.make_mesh((n,), ("data",)), n


@multi_device
class TestRingAllreduce:
    def test_matches_global_sum(self):
        mesh, n = _ring_mesh()
        x = jax.random.normal(jax.random.PRNGKey(0), (n * 4, 16))
        out = jax.shard_map(lambda v: ring_allreduce(v, "data"), mesh=mesh,
                        in_specs=P("data"), out_specs=P("data"))(x)
        # every device's local output block equals the sum of all blocks
        want = x.reshape(n, 4, 16).sum(0)
        got = np.asarray(out).reshape(n, 4, 16)
        for dev in range(n):
            np.testing.assert_allclose(got[dev], np.asarray(want),
                                       rtol=1e-5, atol=1e-5)

    def test_unaligned_chunking_pads(self):
        """Local leading dim not divisible by the ring size exercises the
        internal pad/unpad."""
        mesh, n = _ring_mesh()
        x = jax.random.normal(jax.random.PRNGKey(1), (n * 3, 7))
        out = jax.shard_map(lambda v: ring_allreduce(v, "data"), mesh=mesh,
                        in_specs=P("data"), out_specs=P("data"))(x)
        want = x.reshape(n, 3, 7).sum(0)
        got = np.asarray(out).reshape(n, 3, 7)
        np.testing.assert_allclose(got[0], np.asarray(want), rtol=1e-5,
                                   atol=1e-5)

    def test_matches_psum(self):
        mesh, n = _ring_mesh()
        x = jax.random.normal(jax.random.PRNGKey(2), (n * 2, 8))
        ring = jax.shard_map(lambda v: ring_allreduce(v, "data"), mesh=mesh,
                         in_specs=P("data"), out_specs=P("data"))(x)
        ps = jax.shard_map(lambda v: jax.lax.psum(v, "data"), mesh=mesh,
                       in_specs=P("data"), out_specs=P("data"))(x)
        np.testing.assert_allclose(np.asarray(ring), np.asarray(ps),
                                   rtol=1e-5, atol=1e-5)

    def test_compressed_allreduce_approximates_sum(self):
        mesh, n = _ring_mesh()
        x = jax.random.normal(jax.random.PRNGKey(3), (n * 4, 16))
        out = jax.shard_map(lambda v: compressed_allreduce(v, "data"), mesh=mesh,
                        in_specs=P("data"), out_specs=P("data"))(x)
        want = np.asarray(x.reshape(n, 4, 16).sum(0))
        got = np.asarray(out).reshape(n, 4, 16)[0]
        # int8-on-the-wire: error per element <= n * (scale/2 + f16 eps)
        tol = n * (float(np.abs(x).max()) / 254 + 2e-3)
        np.testing.assert_allclose(got, want, atol=tol)
