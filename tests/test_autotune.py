"""Shape-keyed kernel autotuning: cache round-trips, tuned-tile parity
with the default tiles (interpret-mode candidates may never change
numerics), persistence through serve_view manifests and checkpoint
manifests, and the fingerprint salt that keys the serving jit caches.

Also the honesty guards bench-smoke relies on: `_default_interpret()`
and platform detection must agree with `jax.default_backend()` so a
BENCH record can never label interpret-mode numbers as real-hardware
ones.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.lutq import LutqState, decode_any, init_state
from repro.core.policy import quantize_tree, serve_view
from repro.core.spec import QuantSpec
from repro.kernels import autotune, ops
from repro.kernels.autotune import TileConfig, TuningCache
from repro.kernels.ref import pack4_kin


@pytest.fixture(autouse=True)
def _fresh_tuning_cache():
    """Every test starts and ends with an empty process tuning cache."""
    ops.tuning_cache().clear()
    yield
    ops.tuning_cache().clear()


def _serve_state(Kin, N, bits=4, seed=0):
    w = jax.random.normal(jax.random.PRNGKey(seed), (Kin, N))
    st = init_state(w, QuantSpec(bits=bits, min_size=1))
    return LutqState(w=None, d=st.d, a=st.a)


# the test_kernel_backends parity matrix (M, Kin, N)
SHAPES = [(1, 34, 50), (5, 96, 72), (33, 130, 57), (8, 64, 211)]


class TestCacheRoundTrip:
    def test_json_round_trip(self, tmp_path):
        tc = TuningCache()
        k1 = autotune.make_key("matmul", 8, 2048, 2048, 16, jnp.float32,
                               "fused", "cpu")
        k2 = autotune.make_key("gemv_packed", 1, 512, 1024, 16, jnp.bfloat16,
                               "packed4", "tpu")
        tc.put(k1, TileConfig(bm=8, bn=256, bk=512, strategy="kernel"))
        tc.put(k2, TileConfig(bm=256, bn=128, bk=1024))
        back = TuningCache.from_json_dict(
            json.loads(json.dumps(tc.to_json_dict())))
        assert back.items() == tc.items()

        p = tmp_path / "tuning.json"
        tc.save(p)
        assert TuningCache.load(p).items() == tc.items()

    def test_version_bumps_on_every_mutation(self):
        tc = TuningCache()
        v0 = tc.version
        tc.put("k", TileConfig(bm=8, bn=8, bk=8))
        assert tc.version == v0 + 1
        tc.update({"k2": TileConfig(bm=8, bn=8, bk=8)})
        assert tc.version == v0 + 2
        tc.clear()
        assert tc.version == v0 + 3 and len(tc) == 0

    def test_key_carries_every_tuning_axis(self):
        base = dict(kernel="matmul", M=8, N=64, Kin=128, K=16,
                    dtype=jnp.float32, backend="fused", plat="cpu")
        k0 = autotune.make_key(**base)
        for field, val in [("M", 9), ("N", 65), ("Kin", 130), ("K", 4),
                           ("dtype", jnp.bfloat16), ("backend", "packed4"),
                           ("plat", "tpu"), ("kernel", "gemv_packed")]:
            assert autotune.make_key(**{**base, field: val}) != k0, field


class TestTuneSearch:
    def test_injected_measure_picks_strict_minimum(self):
        """Deterministic winner: candidate order is sorted, ties keep the
        first; the winner lands in the cache under the canonical key."""
        cands = autotune.candidates("matmul", 8, 72, 96, 16, interpret=True)
        assert cands == sorted(
            cands, key=lambda t: (t.bm, t.bn, t.bk, t.strategy))
        target = cands[3]

        def measure(tile):
            return 1.0 if tile == target else 2.0

        tc = TuningCache()
        key, best, timings = autotune.tune(
            "matmul", M=8, N=72, Kin=96, K=16, interpret=True,
            cache=tc, measure=measure)
        assert best == target
        assert tc.get(key) == target
        assert len(timings) == len(cands)
        assert key == autotune.make_key(
            "matmul", 8, 72, 96, 16, jnp.float32, "fused",
            autotune.platform_key(True))

    def test_all_infeasible_keeps_defaults(self):
        """Nothing is recorded when every candidate fails: the search
        raises, and the cache keeps serving the default tiles."""
        tc = TuningCache()
        with pytest.raises(RuntimeError, match="every candidate failed"):
            autotune.tune("matmul", M=8, N=72, Kin=96, K=16, interpret=True,
                          cache=tc, measure=lambda tile: float("inf"))
        assert len(tc) == 0

    def test_interpret_candidates_pin_single_k_step(self):
        """The bit-identity precondition: every interpret candidate keeps
        the whole reduction axis in one k step (bk >= Kin), so the f32
        accumulation grouping matches the default tile exactly."""
        for kernel in ("matmul", "gemv_packed"):
            for M, Kin, N in SHAPES:
                for t in autotune.candidates(kernel, M, N, Kin, 16,
                                             interpret=True):
                    assert t.bk >= Kin, (kernel, M, Kin, N, t)


class TestTunedTileParity:
    def test_tuned_fused_tile_is_bit_identical(self):
        """A non-default tuned tile (small bm/bn) must not change
        lutq_dot's output bits in interpret mode."""
        M, Kin, N = 5, 96, 72
        st = _serve_state(Kin, N)
        x = jax.random.normal(jax.random.PRNGKey(1), (M, Kin))
        default = np.asarray(ops.lutq_dot(x, st, backend="fused"))

        key = autotune.make_key("matmul", M, N, Kin, 16, x.dtype, "fused",
                                autotune.platform_key(ops._default_interpret()))
        ops.tuning_cache().put(key, TileConfig(bm=8, bn=32, bk=512))
        tuned = np.asarray(ops.lutq_dot(x, st, backend="fused"))
        np.testing.assert_array_equal(tuned, default)
        np.testing.assert_allclose(tuned, np.asarray(x @ decode_any(st.d,
                                                                    st.a)),
                                   rtol=2e-4, atol=2e-4)

    def test_explicit_args_override_tuned_tile(self):
        """Caller-passed tile args win over the cache (escape hatch)."""
        M, Kin, N = 5, 96, 72
        st = _serve_state(Kin, N)
        x = jax.random.normal(jax.random.PRNGKey(1), (M, Kin))
        key = autotune.make_key("matmul", M, N, Kin, 16, x.dtype, "fused",
                                autotune.platform_key(ops._default_interpret()))
        ops.tuning_cache().put(key, TileConfig(bm=8, bn=32, bk=512))
        got = ops.lutq_dot(x, st, backend="fused", bm=256, bn=256, bk=512)
        ops.tuning_cache().clear()
        np.testing.assert_array_equal(
            np.asarray(got),
            np.asarray(ops.lutq_dot(x, st, backend="fused", bm=256, bn=256,
                                    bk=512)))

    @pytest.mark.slow
    @pytest.mark.parametrize("M,Kin,N", SHAPES)
    def test_every_interpret_candidate_is_bit_identical(self, M, Kin, N):
        """Exhaustive: each candidate the interpret tuner may pick equals
        the default-tile output bit-for-bit, for both kernels."""
        st = _serve_state(Kin, N)
        x = jax.random.normal(jax.random.PRNGKey(1), (M, Kin))
        default = np.asarray(ops.lutq_dot(x, st, backend="fused"))
        for t in autotune.candidates("matmul", M, N, Kin, 16, interpret=True):
            got = ops.lutq_dot(x, st, backend="fused", bm=t.bm, bn=t.bn,
                               bk=t.bk)
            np.testing.assert_array_equal(np.asarray(got), default, str(t))
        if Kin % 2:
            return
        packed = LutqState(w=None, d=st.d, a=pack4_kin(st.a))
        pdefault = np.asarray(ops.lutq_dot(x, packed, backend="packed4"))
        for t in autotune.candidates("gemv_packed", M, N, Kin, 16,
                                     interpret=True):
            got = ops.lutq_dot(x, packed, backend="packed4", bm=t.bm,
                               bn=t.bn, bk=t.bk)
            np.testing.assert_array_equal(np.asarray(got), pdefault, str(t))

    @pytest.mark.slow
    def test_real_search_round_trips_through_lutq_dot(self):
        """End-to-end: tune() with the real timing loop records a tile
        that lutq_dot then picks up, output unchanged."""
        M, Kin, N = 8, 64, 211
        st = _serve_state(Kin, N)
        x = jax.random.normal(jax.random.PRNGKey(1), (M, Kin))
        default = np.asarray(ops.lutq_dot(x, st, backend="fused"))
        key, best, timings = autotune.tune(
            "matmul", M=M, N=N, Kin=Kin, K=16, reps=1, warmup=0,
            cache=ops.tuning_cache())
        assert ops.tuning_cache().get(key) == best
        assert any(np.isfinite(v) for v in timings.values())
        np.testing.assert_array_equal(
            np.asarray(ops.lutq_dot(x, st, backend="fused")), default)


def _tree():
    k = jax.random.PRNGKey(0)
    return {"layers": {"mlp": {"wi": {"kernel": jax.random.normal(
        k, (64, 128))}}}}


class TestPersistence:
    def test_serve_view_manifest_carries_tuning_cache(self):
        q = quantize_tree(_tree(), QuantSpec(bits=4, min_size=1))
        _, man = serve_view(q, with_manifest=True)
        assert "__tuning_cache__" not in man  # empty cache -> no entry

        key = autotune.make_key("matmul", 8, 128, 64, 16, jnp.float32,
                                "fused", "cpu")
        tile = TileConfig(bm=8, bn=128, bk=512)
        ops.tuning_cache().put(key, tile)
        _, man = serve_view(q, with_manifest=True)
        carried = TuningCache.from_json_dict(man["__tuning_cache__"])
        assert carried.get(key) == tile
        # and the whole manifest (tiles included) survives JSON
        assert json.loads(json.dumps(man)) == man

    def test_checkpoint_manifest_round_trip(self, tmp_path):
        from repro.checkpoint.ckpt import load_tuning, save

        q = quantize_tree(_tree(), QuantSpec(bits=4, min_size=1))
        key = autotune.make_key("matmul", 8, 128, 64, 16, jnp.float32,
                                "fused", "cpu")
        tile = TileConfig(bm=8, bn=128, bk=512)
        tc = TuningCache()
        tc.put(key, tile)
        save(q, str(tmp_path), 3, tuning=tc)
        back = load_tuning(str(tmp_path))
        assert back.get(key) == tile
        # untuned save -> no record, load_tuning -> None
        save(q, str(tmp_path / "plain"), 1)
        assert load_tuning(str(tmp_path / "plain")) is None

    def test_async_checkpointer_snapshots_live_cache(self, tmp_path):
        from repro.checkpoint.ckpt import AsyncCheckpointer, load_tuning

        tc = TuningCache()
        tc.put("k", TileConfig(bm=8, bn=8, bk=8))
        ck = AsyncCheckpointer(str(tmp_path), tuning=tc)
        ck.save(_tree(), 5)
        ck.wait()
        assert load_tuning(str(tmp_path)).get("k") == TileConfig(bm=8, bn=8,
                                                                 bk=8)


class TestJitCacheSalting:
    def test_fingerprint_tracks_process_cache(self):
        f0 = ops.tuning_fingerprint()
        ops.tuning_cache().put("k", TileConfig(bm=8, bn=8, bk=8))
        assert ops.tuning_fingerprint() == f0 + 1

    def test_decode_fn_retraces_on_tuning_update(self):
        """A tuning-cache mutation must invalidate the cached serving
        jits — otherwise a tuned tile lands after the first generate and
        silently never applies."""
        from repro.configs import get_config
        from repro.models.reduce import reduced
        from repro.runtime.serving import decode_fn, prefill_fn

        cfg = reduced(get_config("h2o-danube-1.8b"))
        f1 = decode_fn(cfg)
        p1 = prefill_fn(cfg, 32)
        assert decode_fn(cfg) is f1  # stable while the cache is quiet
        ops.tuning_cache().put("k", TileConfig(bm=8, bn=8, bk=8))
        assert decode_fn(cfg) is not f1
        assert prefill_fn(cfg, 32) is not p1


class TestPlatformGuards:
    """bench-smoke honesty: BENCH records label platform/interpret from
    these helpers, so they must track jax.default_backend exactly."""

    def test_default_interpret_matches_backend(self):
        assert ops._default_interpret() == (jax.default_backend() != "tpu")
        assert autotune.default_interpret() == ops._default_interpret()

    def test_platform_key_never_masquerades(self):
        plat = jax.default_backend()
        assert autotune.platform() == plat
        # not forcing interpret keys as the real platform
        assert autotune.platform_key(False) == plat
        # forcing interpret on a real TPU must NOT key (or report) as tpu
        if plat == "tpu":
            assert autotune.platform_key(True) == "interpret"
        else:
            assert autotune.platform_key(True) == plat

    def test_bench_record_is_honest(self):
        """The BENCH writer stamps platform/interpret from the same
        helpers the kernels dispatch on."""
        import importlib.util
        from pathlib import Path

        root = Path(__file__).resolve().parent.parent
        spec = importlib.util.spec_from_file_location(
            "kernel_bench", root / "benchmarks" / "kernel_bench.py")
        kb = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(kb)
        rec = kb.bench_backends(quick=True, reps=1, warmup=0, tune=False)
        assert rec["platform"] == jax.default_backend()
        assert rec["interpret"] == ops._default_interpret()
        assert rec["reps"] == 1
        for b in rec["backends"].values():
            assert b["measured_over_model"] == pytest.approx(
                b["us"] / b["v5e_model_us"])


class TestLeafShapes:
    def test_tree_shapes_cover_fused_and_transposed(self):
        pol = QuantSpec(bits=4, min_size=1)
        tree = {"layers": {"mlp": {"wi": {"kernel": jax.random.normal(
                    jax.random.PRNGKey(0), (64, 128))}}},
                "embed": {"table": jax.random.normal(
                    jax.random.PRNGKey(1), (96, 64))}}
        sv = serve_view(quantize_tree(tree, pol))
        recs = autotune.leaf_shapes_for_tree(sv, batch_m=4)
        by_shape = {(r["M"], r["Kin"], r["N"]): r for r in recs}
        assert (4, 64, 128) in by_shape          # the mlp kernel
        assert (4, 96, 64) in by_shape           # embed.table forward
        assert (4, 64, 96) in by_shape           # tied-logits transpose
        assert any(p.endswith(".T")
                   for p in by_shape[(4, 64, 96)]["paths"])

    def test_tune_tree_fills_cache_with_injected_speed(self, monkeypatch):
        # patch the timing loop so tune_tree is instant
        monkeypatch.setattr(autotune, "measure_call",
                            lambda fn, *a, **k: 1.0)
        pol = QuantSpec(bits=4, min_size=1)
        sv = serve_view(quantize_tree(_tree(), pol))
        lines = []
        tc = autotune.tune_tree(sv, batch_m=8, cache=TuningCache(),
                                emit=lines.append)
        assert len(tc) == len(autotune.leaf_shapes_for_tree(sv, batch_m=8))
        assert len(lines) == len(tc)
        for key, tile in tc.items():
            assert isinstance(tile, TileConfig)
            assert key.split("|")[0] in ("matmul", "gemv_packed")
