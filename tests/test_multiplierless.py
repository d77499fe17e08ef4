"""Multiplier-less serving: pow2 sign+exponent planes, the shift-add
kernel backend, frozen activation scales, and the compiled-HLO multiply
audit.

The load-bearing claims:
  * every pow2-encoded serve leaf decodes to exactly ±2^k or 0;
  * the Pallas shift-add kernel is token-identical (bitwise) to the
    pure-XLA integer oracle — same quantization, same int32
    accumulation — under every tiling, so ``backend="pow2"`` and
    ``backend="decode"`` on an encoded leaf agree exactly;
  * a compiled ``serving_pow2`` forward contains no fp multiplies in
    the quantized matmul path (StableHLO audit, kernels/audit.py);
  * calibration freezes per-leaf activation scales that persist through
    serve views and checkpoints.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis_compat import given, settings, st

from repro.core.lutq import (
    LutqState,
    decode_any,
    init_state,
    pow2_decode,
    pow2_encode,
)
from repro.core.policy import backend_manifest, quantize_tree, serve_view
from repro.core.rules import serving_pow2
from repro.core.spec import SERVING_POW2, QuantSpec
from repro.kernels import audit, ops
from repro.kernels.ref import lutq_shift_ref, pow2_shift_scale, pow2_shift_weights


def _pow2_state(Kin, N, bits=4, seed=0, act=None):
    w = jax.random.normal(jax.random.PRNGKey(seed), (Kin, N))
    st_ = init_state(w, QuantSpec(bits=bits, constraint="pow2", min_size=1))
    return LutqState(w=None, d=pow2_encode(st_.d), a=st_.a, act=act)


# same odd-shape matrix as test_kernel_backends (gemv row, non-tile
# multiples) — the kernel pads, the oracle does not, parity is bitwise
SHAPES = [(1, 34, 50), (5, 96, 72), (33, 130, 57), (8, 64, 211)]


class TestPow2Encoding:
    @settings(deadline=None, max_examples=50)
    @given(st.lists(st.floats(min_value=-64.0, max_value=64.0,
                              allow_nan=False), min_size=2, max_size=16))
    def test_every_decoded_entry_is_pow2_or_zero(self, vals):
        code = pow2_encode(jnp.asarray(vals, jnp.float32))
        assert code.dtype == jnp.int8
        dec = np.asarray(pow2_decode(code), np.float64)
        nz = dec[dec != 0]
        assert np.all(np.log2(np.abs(nz)) == np.round(np.log2(np.abs(nz))))

    def test_serve_leaf_decodes_to_pow2(self):
        """Acceptance: every pow2 serve leaf is exactly ±2^k or 0."""
        st_ = _pow2_state(64, 48)
        q = np.asarray(decode_any(st_.d, st_.a), np.float64)
        nz = np.abs(q[q != 0])
        assert np.all(np.log2(nz) == np.round(np.log2(nz)))

    def test_shift_plane_reconstructs_decode(self):
        """wsh * scale == pow2_decode(code): the kernel's int32 shifted
        weights plus one fp scale are a lossless factorization."""
        st_ = _pow2_state(64, 48)
        wsh = pow2_shift_weights(st_.d)
        scale = pow2_shift_scale(st_.d)
        np.testing.assert_array_equal(
            np.asarray(wsh.astype(jnp.float32) * scale),
            np.asarray(pow2_decode(st_.d)))

    def test_encode_roundtrip_on_pow2_constrained_dict(self):
        w = jax.random.normal(jax.random.PRNGKey(3), (128, 32))
        st_ = init_state(w, QuantSpec(bits=4, constraint="pow2", min_size=1))
        np.testing.assert_array_equal(
            np.asarray(pow2_decode(pow2_encode(st_.d))), np.asarray(st_.d))


class TestShiftKernelParity:
    @pytest.mark.parametrize("M,Kin,N", SHAPES)
    def test_kernel_bitwise_matches_oracle(self, M, Kin, N):
        st_ = _pow2_state(Kin, N)
        x = jax.random.normal(jax.random.PRNGKey(1), (M, Kin))
        want = ops.lutq_dot(x, st_, backend="decode")  # integer oracle
        got = ops.lutq_dot(x, st_, backend="pow2")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_transpose_rhs_bitwise(self):
        st_ = _pow2_state(96, 211)
        x = jax.random.normal(jax.random.PRNGKey(2), (7, 211))
        want = ops.lutq_dot(x, st_, backend="decode", transpose_rhs=True)
        got = ops.lutq_dot(x, st_, backend="pow2", transpose_rhs=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_frozen_act_pair_bitwise(self):
        act = jnp.array([0.021, 127.0], jnp.float32)
        st_ = _pow2_state(64, 48, act=act)
        x = jax.random.normal(jax.random.PRNGKey(4), (5, 64))
        want = ops.lutq_dot(x, st_, backend="decode")
        got = ops.lutq_dot(x, st_, backend="pow2")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_raw_kernel_matches_ref(self):
        """lutq_shift (Pallas) == lutq_shift_ref on tile-exact shapes."""
        st_ = _pow2_state(512, 256)
        wsh = pow2_shift_weights(st_.d)
        xq = jax.random.randint(jax.random.PRNGKey(5), (256, 512), -127, 128,
                                dtype=jnp.int8)
        want = lutq_shift_ref(xq, st_.a, wsh)
        got = ops.lutq_shift(xq, st_.a, wsh, bm=256, bn=256, bk=512)
        assert got.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_raw_kernel_wide_shift_planes(self):
        """Shifts past 6 bits land in the kernel's higher 7-bit int8
        planes; the int32 accumulator stays exact."""
        K = 16
        wsh = jnp.array([0, 1, -1, 2, -4, 64, 128, -256, 1 << 13,
                         -(1 << 14), 1 << 16, 512, -8, 16, -(1 << 7), 32],
                        jnp.int32)[:K]
        a = jax.random.randint(jax.random.PRNGKey(6), (128, 128), 0, K,
                               jnp.int8)
        xq = jax.random.randint(jax.random.PRNGKey(7), (8, 128), -127, 128,
                                dtype=jnp.int8)
        got = ops.lutq_shift(xq, a, wsh, bm=8, bn=128, bk=64)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(lutq_shift_ref(xq, a, wsh)))

    def test_quant_noise_bounded(self):
        """pow2 output error vs the unquantized-activation product is
        bounded by the int8 step (sanity that int8 act quant is sane)."""
        st_ = _pow2_state(64, 48)
        x = jax.random.normal(jax.random.PRNGKey(6), (5, 64))
        exact = x @ decode_any(st_.d, st_.a)
        got = ops.lutq_dot(x, st_, backend="pow2")
        rel = np.abs(np.asarray(got - exact)).max() / (
            np.abs(np.asarray(exact)).max() + 1e-9)
        assert rel < 0.05, rel


class TestResolution:
    def test_pow2_rung(self):
        st_ = _pow2_state(64, 48)
        assert ops.resolve_backend(st_, "auto") == "pow2"
        assert ops.resolve_backend(st_, "pow2") == "pow2"
        assert ops.resolve_backend(st_, "decode") == "decode"
        # transposed readout stays on the shift kernel
        assert ops.resolve_backend(st_, "auto", transpose_rhs=True) == "pow2"

    def test_pow2_on_float_leaf_degrades(self):
        w = jax.random.normal(jax.random.PRNGKey(0), (64, 48))
        st_ = init_state(w, QuantSpec(bits=4, min_size=1))
        serve = LutqState(w=None, d=st_.d, a=st_.a)
        # float dictionary: the shift kernel does not apply -> fused
        assert ops.resolve_backend(serve, "pow2") == "fused"

    def test_stacked_pow2_slices_dispatch(self):
        st_ = _pow2_state(64, 48)
        stk = LutqState(w=None, d=jnp.stack([st_.d] * 3),
                        a=jnp.stack([st_.a] * 3))
        assert ops.resolve_backend(stk, "auto") == "decode"
        assert ops.resolve_backend(stk, "auto", sliced=True) == "pow2"

    def test_overflow_guard_keeps_float_dict(self):
        """A dictionary spanning the full exponent range cannot promise
        an int32-safe accumulator at Kin=1024 -> serve_view keeps the
        float dictionary (degrades to the fused ladder, still correct)."""
        d = jnp.array([2.0 ** -14, 2.0 ** -3, 2.0 ** 3, 2.0 ** 15])
        a = jax.random.randint(jax.random.PRNGKey(0), (1024, 8), 0, 4,
                               dtype=jnp.int8)
        tree = {"x": {"kernel": LutqState(w=None, d=d, a=a)}}
        pol = serving_pow2()
        sv = serve_view(tree, policy=pol)
        assert sv["x"]["kernel"].d.dtype != jnp.int8


class TestMultiplyAudit:
    def test_oracle_lowering_is_integer(self):
        """Acceptance: zero fp multiplies in the quantized matmul path of
        a compiled pow2 forward; the s32 accumulation is present."""
        st_ = _pow2_state(64, 48)
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 64))
        rep = audit.audit_multiplierless(
            lambda x, s: ops.lutq_dot(x, s, backend="decode"), x, st_,
            weight_shapes=[(64, 48)])
        assert not rep["fp_dots"]
        assert rep["int_dots"]
        # fp multiplies only at the boundary: quant (M,Kin) / epilogue (M,N)
        for m in rep["fp_multiplies"]:
            assert m["elems"] <= 8 * 64, m

    def test_kernel_lowering_is_integer(self):
        st_ = _pow2_state(64, 48)
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 64))
        rep = audit.audit_multiplierless(
            lambda x, s: ops.lutq_dot(x, s, backend="pow2"), x, st_,
            weight_shapes=[(64, 48)])
        assert rep["int_dots"]

    def test_float_decode_fails_audit(self):
        w = jax.random.normal(jax.random.PRNGKey(0), (64, 48))
        st_ = init_state(w, QuantSpec(bits=4, min_size=1))
        serve = LutqState(w=None, d=st_.d, a=st_.a)
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 64))
        with pytest.raises(AssertionError, match="decoded-weight matmul"):
            audit.audit_multiplierless(
                lambda x, s: ops.lutq_dot(x, s, backend="decode"), x, serve,
                weight_shapes=[(64, 48)])

    def test_weight_dims_collected_from_params(self):
        st_ = _pow2_state(64, 48)
        dims = audit.quantized_weight_dims({"a": {"kernel": st_}})
        assert (64, 48) in dims and (48, 64) in dims


class TestActRegime:
    def test_dot_kernel_dynamic_act_matches_old_placement(self):
        """act_bits at the boundary == historical fake_quant-then-call."""
        from repro.core.actquant import fake_quant
        from repro.nn.linear import dot_kernel
        w = jax.random.normal(jax.random.PRNGKey(0), (64, 48))
        x = jax.random.normal(jax.random.PRNGKey(1), (5, 64))
        np.testing.assert_array_equal(
            np.asarray(dot_kernel(x, w, act_bits=8)),
            np.asarray(dot_kernel(fake_quant(x, 8), w)))

    def test_frozen_fake_quant_matches_pow2_internal_quant(self):
        """fake_quant_frozen(x)@decoded == dequantized pow2 path: the
        fused-with-frozen-scales forward and the shift-add forward
        quantize activations identically."""
        from repro.core.actquant import fake_quant_frozen
        act = jnp.array([0.03, 127.0], jnp.float32)
        st_ = _pow2_state(64, 48, act=act)
        x = jax.random.normal(jax.random.PRNGKey(2), (5, 64))
        want = fake_quant_frozen(x, act) @ decode_any(st_.d, st_.a)
        got = ops.lutq_dot(x, st_, backend="pow2")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_fake_quant_frozen_ste_gradient(self):
        from repro.core.actquant import fake_quant_frozen
        act = jnp.array([0.1, 127.0], jnp.float32)
        x = jnp.linspace(-1, 1, 64)
        g = jax.grad(lambda x: jnp.sum(fake_quant_frozen(x, act) ** 2))(x)
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(2 * fake_quant_frozen(x, act)),
            atol=1e-6)

    def test_capture_and_apply_scales(self):
        from repro.core.actquant import (
            apply_act_scales,
            capture_act_scales,
            tag_act_capture,
        )
        from repro.nn.linear import dot_kernel
        w = jax.random.normal(jax.random.PRNGKey(0), (64, 48))
        st_ = init_state(w, QuantSpec(bits=4, constraint="pow2", min_size=1))
        tree = {"layers": {"mlp": {"wi": {
            "kernel": LutqState(w=None, d=st_.d, a=st_.a)}}}}
        x = jax.random.normal(jax.random.PRNGKey(1), (5, 64)) * 3.0
        tagged = tag_act_capture(tree)

        @jax.jit
        def fwd(t, x):
            return dot_kernel(x, t["layers"]["mlp"]["wi"]["kernel"])

        with capture_act_scales() as rec:
            jax.block_until_ready(fwd(tagged, x))
        assert rec["layers/mlp/wi/kernel"] == pytest.approx(
            float(jnp.max(jnp.abs(x))), rel=1e-6)
        out = apply_act_scales(tree, rec, quant=serving_pow2())
        act = out["layers"]["mlp"]["wi"]["kernel"].act
        assert act is not None and act.shape == (2,)
        assert float(act[1]) == 127.0
        assert float(act[0]) == pytest.approx(
            float(jnp.max(jnp.abs(x))) / 127.0, rel=1e-6)

    def test_unmatched_rules_left_alone(self):
        from repro.core.actquant import apply_act_scales
        w = jax.random.normal(jax.random.PRNGKey(0), (64, 48))
        st_ = init_state(w, QuantSpec(bits=4, min_size=1))
        tree = {"x": {"kernel": LutqState(w=None, d=st_.d, a=st_.a)}}
        # act_frozen=False spec: no pair installed even with a record
        out = apply_act_scales(tree, {"x/kernel": 3.0},
                               quant=QuantSpec(bits=4, min_size=1))
        assert out["x"]["kernel"].act is None


class TestCheckpointAndManifest:
    def test_ckpt_roundtrips_act_and_pow2_plane(self, tmp_path):
        from repro.checkpoint import ckpt
        act = jnp.array([0.05, 127.0], jnp.float32)
        st_ = _pow2_state(64, 48, act=act)
        tree = {"layers": {"wi": {"kernel": st_}}}
        ckpt.save(tree, str(tmp_path), 0)
        back = ckpt.restore(str(tmp_path))[0]
        leaf = back["layers"]["wi"]["kernel"]
        assert leaf.d.dtype == jnp.int8
        np.testing.assert_array_equal(np.asarray(leaf.d), np.asarray(st_.d))
        np.testing.assert_array_equal(np.asarray(leaf.act), np.asarray(act))

    def test_serve_view_manifest_records_encoding(self):
        w = jax.random.normal(jax.random.PRNGKey(0), (64, 96))  # >= min_size
        tree = {"layers": {"mlp": {"wi": {"kernel": w}}}}
        pol = serving_pow2()
        q = quantize_tree(tree, pol)
        sv, man = serve_view(q, policy=pol, with_manifest=True)
        rec = man["layers/mlp/wi/kernel"]
        assert rec["backend"] == "pow2"
        assert rec["encoding"] == "pow2"
        assert rec["act_frozen"] is False  # not calibrated
        assert sv["layers"]["mlp"]["wi"]["kernel"].d.dtype == jnp.int8
        # standalone manifest of the tree agrees (policy for `requested`)
        assert backend_manifest(sv, policy=pol) == man

    def test_spec_validation(self):
        with pytest.raises(ValueError):
            QuantSpec(bits=4, backend="pow2")  # needs pow2 constraint
        with pytest.raises(ValueError):
            QuantSpec(bits=4, act_bits=0)
        assert SERVING_POW2.act_frozen and SERVING_POW2.act_bits == 8


class TestShiftAutotune:
    def test_tune_shift_kernel_records_pow2_backend(self):
        from repro.kernels import autotune
        ops.tuning_cache().clear()
        try:
            key, tile, timings = autotune.tune(
                "shift", M=8, N=128, Kin=128, K=16, interpret=True,
                cache=ops.tuning_cache(),
                measure=lambda t: float(t.bm + t.bn + t.bk))
            assert "pow2" in key and "int8" in key
            assert ops.tuning_cache().get(key) == tile
        finally:
            ops.tuning_cache().clear()


# -- full-model serving_pow2 path ------------------------------------------

def _pow2_setup(arch="h2o-danube-1.8b", calibrate=True):
    from repro.configs import get_config
    from repro.models import api
    from repro.models.reduce import reduced
    cfg = reduced(get_config(arch)).replace(
        quant=serving_pow2(), act_bits=8, remat=False)
    params, axes = api.init(jax.random.PRNGKey(0), cfg)
    q = api.quantize(params, cfg, axes)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab)
    if calibrate:
        q = api.calibrate(q, cfg, {"tokens": toks})
    pol = api.resolved_policy(cfg)
    sv, man = serve_view(q, policy=pol, with_manifest=True)
    return cfg, sv, man, {"tokens": toks}


class TestServingPow2EndToEnd:
    def test_prefill_kernel_bitwise_matches_oracle(self):
        from repro.models import api
        cfg, sv, man, batch = _pow2_setup()
        body = {k: v for k, v in man.items()
                if not k.startswith("__") and v["encoding"] == "pow2"}
        assert body and all(v["act_frozen"] for v in body.values())
        outs = {}
        for be in ("decode", "auto"):
            logits, _ = api.prefill(sv, cfg.replace(kernel_backend=be), batch)
            outs[be] = np.asarray(logits, np.float32)
        np.testing.assert_array_equal(outs["auto"], outs["decode"])

    def test_generate_token_identical(self):
        from repro.runtime.serving import generate
        cfg, sv, _, batch = _pow2_setup()
        out_d = generate(sv, cfg, batch, steps=4, backend="decode")
        out_p = generate(sv, cfg, batch, steps=4, backend="auto")
        np.testing.assert_array_equal(np.asarray(out_d), np.asarray(out_p))

    def test_forward_audit_no_fp_multiply_on_quantized_path(self):
        """Acceptance: the compiled serve forward's quantized matmuls are
        integer — audited on the lowered StableHLO of the real jit."""
        from repro.models import api
        cfg, sv, _, batch = _pow2_setup()
        cfg = cfg.replace(kernel_backend="decode")  # oracle: pure XLA
        rep = audit.audit_multiplierless(
            lambda p, t: api.prefill(p, cfg, {"tokens": t})[0],
            sv, batch["tokens"], params=sv)
        assert rep["int_dots"]

    @pytest.mark.slow
    def test_engine_parity(self):
        """Ragged requests through a 2-slot engine decode
        token-identically on the shift kernel vs the integer oracle."""
        from repro.runtime.engine import Engine
        cfg, sv, _, _ = _pow2_setup()
        toks = np.asarray(jax.random.randint(jax.random.PRNGKey(7), (3, 10),
                                             0, cfg.vocab), np.int32)
        outs = {}
        for be in ("decode", "auto"):
            eng = Engine(sv, cfg.replace(kernel_backend=be), capacity=2,
                         max_len=20)
            for i, L in enumerate((10, 6, 8)):
                eng.submit(toks[i, :L], max_new=4)
            outs[be] = eng.run()
        for a, b in zip(outs["decode"], outs["auto"]):
            np.testing.assert_array_equal(a["tokens"], b["tokens"])
