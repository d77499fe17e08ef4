"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.kernels import ops
from repro.kernels.ref import (
    kmeans_stats_ref,
    lutq_gemv_packed_ref,
    lutq_matmul_ref,
    pack4,
    unpack4,
)


def _mk(shape, seed=0, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(seed), shape).astype(dtype)


class TestLutqMatmul:
    @pytest.mark.parametrize("M,Kin,N", [(8, 128, 128), (256, 512, 256),
                                         (64, 1024, 512), (128, 256, 384)])
    @pytest.mark.parametrize("K", [4, 16, 256])
    def test_matches_ref(self, M, Kin, N, K):
        x = _mk((M, Kin), 1)
        a = jax.random.randint(jax.random.PRNGKey(2), (Kin, N), 0, K, jnp.int8)
        d = jnp.sort(_mk((K,), 3))
        got = ops.lutq_matmul(x, a, d, bm=min(128, M), bn=128, bk=128,
                              interpret=True)
        want = lutq_matmul_ref(x, a, d)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-3)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        x = _mk((32, 256), 1, dtype)
        a = jax.random.randint(jax.random.PRNGKey(2), (256, 128), 0, 16, jnp.int8)
        d = jnp.sort(_mk((16,), 3))
        got = ops.lutq_matmul(x, a, d, bm=32, bn=128, bk=128, interpret=True)
        want = lutq_matmul_ref(x, a, d)
        tol = 1e-4 if dtype == jnp.float32 else 3e-2
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=tol, atol=tol)

    def test_select_decode_split_k(self):
        """The raw kernel's compare-and-select decode over a split
        reduction (two k steps)."""
        from repro.kernels.lutq_matmul import lutq_matmul as raw
        x = _mk((16, 128), 5)
        a = jax.random.randint(jax.random.PRNGKey(6), (128, 128), 0, 16, jnp.int8)
        d = jnp.sort(_mk((16,), 7))
        got = raw(x, a, d, bm=16, bn=128, bk=64, interpret=True)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(lutq_matmul_ref(x, a, d)),
                                   rtol=1e-5, atol=1e-4)

    def test_select_decode_k256_wraps_int8(self):
        """K=256 assignments stored as int8 (128..255 read as negative)
        decode like jnp.take's wrap: select_decode is exactly d[a]."""
        from repro.kernels.lutq_matmul import select_decode, smem_row
        a = jax.random.randint(jax.random.PRNGKey(8), (64, 128), 0, 256,
                               jnp.int32).astype(jnp.int8)
        d = jnp.sort(_mk((256,), 9))
        got = select_decode(a.astype(jnp.int32), smem_row(d))
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(jnp.take(d, a.astype(jnp.int32))))

    @given(st.integers(0, 50))
    @settings(max_examples=8, deadline=None)
    def test_property_random_blocks(self, seed):
        g = np.random.default_rng(seed)
        M = int(g.choice([16, 32, 64]))
        Kin = int(g.choice([128, 256]))
        N = int(g.choice([128, 256]))
        x = _mk((M, Kin), seed)
        a = jax.random.randint(jax.random.PRNGKey(seed), (Kin, N), 0, 16, jnp.int8)
        d = jnp.sort(_mk((16,), seed + 1))
        got = ops.lutq_matmul(x, a, d, bm=16, bn=128, bk=128, interpret=True)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(lutq_matmul_ref(x, a, d)),
                                   rtol=1e-5, atol=1e-4)


class TestPacking:
    def test_pack_unpack_roundtrip(self):
        a = jax.random.randint(jax.random.PRNGKey(0), (64, 32), 0, 16, jnp.int8)
        np.testing.assert_array_equal(np.asarray(unpack4(pack4(a))), np.asarray(a))


class TestGemvPacked:
    @pytest.mark.parametrize("K", [16, 8, 3])
    @pytest.mark.parametrize("B,Kin,N", [(1, 256, 256), (8, 512, 128),
                                         (16, 1024, 512), (32, 512, 384),
                                         (256, 256, 256)])
    def test_matches_ref(self, B, Kin, N, K):
        """Dictionaries of 16, 8 and 3 entries (the padded entries are
        never indexed), decode to prefill batches, two k steps."""
        x = _mk((B, Kin), 1)
        a = jax.random.randint(jax.random.PRNGKey(2), (Kin, N), 0, K, jnp.int8)
        packed = pack4(a)
        d = jnp.sort(_mk((K,), 3))
        got = ops.lutq_gemv_packed(x, packed, d, bn=128, bk=128, interpret=True)
        want = lutq_gemv_packed_ref(x, packed, d)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-4)
        # and the packed path equals the unpacked decode exactly
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(lutq_matmul_ref(x, a, d)),
                                   rtol=1e-5, atol=1e-4)

    @pytest.mark.parametrize("K", [16, 8, 3])
    @pytest.mark.parametrize("bn,bk", [(128, 128), (256, 512)])
    def test_decodes_d_of_a_bit_for_bit(self, K, bn, bk):
        """Identity rows read the decoded tile back: each nibble plane
        decodes to exactly ``d[a]`` in x's dtype, whatever the tile."""
        Kin, N = 512, 256
        a = jax.random.randint(jax.random.PRNGKey(4), (Kin, N), 0, K, jnp.int8)
        d = jnp.sort(_mk((K,), 5))
        got = ops.lutq_gemv_packed(jnp.eye(Kin, dtype=jnp.bfloat16), pack4(a),
                                   d, bn=bn, bk=bk, interpret=True)
        want = jnp.take(d, a.astype(jnp.int32)).astype(jnp.bfloat16)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(want.astype(jnp.float32)))

    @pytest.mark.parametrize("M,Kin,N", [(5, 300, 200), (33, 130, 57)])
    def test_lutq_dot_pads_onto_the_grid(self, M, Kin, N):
        """``lutq_dot`` zero-pads rows, the reduction and the output
        lanes onto the kernel's tiles and slices the pad off."""
        from repro.core.lutq import LutqState
        from repro.kernels.ref import pack4_kin
        x = _mk((M, Kin), 6)
        a = jax.random.randint(jax.random.PRNGKey(7), (Kin, N), 0, 16, jnp.int8)
        d = jnp.sort(_mk((16,), 8))
        st = LutqState(w=None, d=d, a=pack4_kin(a))
        got = ops.lutq_dot(x, st, backend="packed4", bm=8, bn=128, bk=256,
                           interpret=True)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(lutq_matmul_ref(x, a, d)),
                                   rtol=1e-5, atol=1e-4)

    @pytest.mark.parametrize("N,Kin,bn,bk", [
        (6912, 2560, 768, 2560),      # danube gate/up
        (2560, 6912, 640, 2304),      # danube down
        (32000, 2560, 640, 2560),     # danube head
        (14336, 5120, 1024, 2560),    # Nemo gate/up
        (5120, 14336, 1024, 2048),    # Nemo down
        (131072, 5120, 1024, 2560),   # Nemo head
        (57, 130, 128, 256),          # padded up to the hardware tiling
    ])
    def test_default_tile_divides_the_shape(self, N, Kin, bn, bk):
        """The packed default tile is the widest under the cap that
        divides N and Kin (rounded up to 128 lanes and 256 rows): no
        grid step decodes padding beyond the hardware tiling."""
        t = ops.default_tile("packed4", N, Kin)
        assert (t.bn, t.bk) == (bn, bk)
        assert -(-N // 128) * 128 % t.bn == 0 and t.bn % 128 == 0
        assert -(-Kin // 256) * 256 % t.bk == 0 and t.bk % 256 == 0
        assert ops.default_tile("fused", N, Kin) == ops.DEFAULT_TILE

    def test_weight_bytes_are_quartered(self):
        Kin, N = 512, 256
        a = jax.random.randint(jax.random.PRNGKey(0), (Kin, N), 0, 16, jnp.int8)
        packed = pack4(a)
        bf16_bytes = Kin * N * 2
        assert packed.size * packed.dtype.itemsize == bf16_bytes // 4


class TestKmeansKernel:
    @pytest.mark.parametrize("N,K", [(4096, 4), (8192, 16), (16384, 256),
                                     (4096, 3)])
    def test_matches_ref(self, N, K):
        w = _mk((N,), 1)
        d = jnp.sort(_mk((K,), 2))
        a, sums, counts = ops.kmeans_stats(w, d, bn=2048, interpret=True)
        a_r, s_r, c_r = kmeans_stats_ref(w, d)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(a_r))
        np.testing.assert_allclose(np.asarray(sums), np.asarray(s_r),
                                   rtol=1e-4, atol=1e-3)
        np.testing.assert_allclose(np.asarray(counts), np.asarray(c_r))

    def test_fused_step_matches_core_kmeans(self):
        from repro.core.lutq import kmeans_update
        from repro.core.spec import QuantSpec
        w = _mk((8192,), 5)
        d0 = jnp.sort(_mk((16,), 6))
        spec = QuantSpec(bits=4, kmeans_iters=1)
        d_core, a_core = kmeans_update(w, d0, spec)
        a_k, d_k = ops.kmeans_step_fused(w, d0, bn=2048, interpret=True)
        np.testing.assert_allclose(np.asarray(d_k), np.asarray(d_core),
                                   rtol=1e-5, atol=1e-5)

    def test_counts_sum_to_n(self):
        w = _mk((4096,), 9)
        d = jnp.sort(_mk((8,), 10))
        _, _, counts = ops.kmeans_stats(w, d, bn=1024, interpret=True)
        assert float(counts.sum()) == 4096.0
