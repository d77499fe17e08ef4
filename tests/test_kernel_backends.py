"""Kernel execution-backend layer: lutq_dot parity, backend resolution,
serve_view manifests, and end-to-end serve-mode dispatch."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.lutq import LutqState, decode_any, init_state
from repro.core.policy import backend_manifest, quantize_tree, serve_view
from repro.core.rules import QuantPolicy, QuantRule
from repro.core.spec import QuantSpec
from repro.kernels import ops
from repro.kernels.ref import pack4_kin, unpack4_kin


def _serve_state(Kin, N, bits=4, seed=0):
    w = jax.random.normal(jax.random.PRNGKey(seed), (Kin, N))
    st = init_state(w, QuantSpec(bits=bits, min_size=1))
    return LutqState(w=None, d=st.d, a=st.a)


# Odd shapes on purpose: none are multiples of the default kernel tiles,
# M=1 is the gemv case, Kin=130/34 are not multiples of bk.
SHAPES = [(1, 34, 50), (5, 96, 72), (33, 130, 57), (8, 64, 211)]


class TestLutqDotParity:
    @pytest.mark.parametrize("M,Kin,N", SHAPES)
    @pytest.mark.parametrize("backend", ["decode", "fused"])
    def test_matches_dense_reference(self, M, Kin, N, backend):
        st = _serve_state(Kin, N)
        x = jax.random.normal(jax.random.PRNGKey(1), (M, Kin))
        want = x @ decode_any(st.d, st.a)
        got = ops.lutq_dot(x, st, backend=backend)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("M,Kin,N", [(1, 34, 50), (5, 96, 72), (8, 64, 211)])
    def test_packed4_matches_reference(self, M, Kin, N):
        st = _serve_state(Kin, N)  # K=16 -> packable
        packed = LutqState(w=None, d=st.d, a=pack4_kin(st.a))
        np.testing.assert_array_equal(np.asarray(unpack4_kin(packed.a)),
                                      np.asarray(st.a))
        x = jax.random.normal(jax.random.PRNGKey(1), (M, Kin))
        want = x @ decode_any(st.d, st.a)
        for backend in ("auto", "packed4", "decode"):
            got = ops.lutq_dot(x, packed, backend=backend)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-4, atol=2e-4, err_msg=backend)

    def test_transposed_tied_logits(self):
        """x @ d[A].T — the tied-embedding readout orientation."""
        st = _serve_state(96, 211)
        x = jax.random.normal(jax.random.PRNGKey(2), (7, 211))
        want = x @ decode_any(st.d, st.a).T
        got = ops.lutq_dot(x, st, backend="fused", transpose_rhs=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)

    def test_leading_batch_dims_and_dtype(self):
        st = _serve_state(64, 48, bits=2)
        x = jax.random.normal(jax.random.PRNGKey(3), (2, 3, 64), jnp.bfloat16)
        got = ops.lutq_dot(x, st, backend="fused")
        assert got.shape == (2, 3, 48) and got.dtype == jnp.bfloat16
        want = jnp.matmul(x, decode_any(st.d, st.a).astype(jnp.bfloat16))
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=5e-2, atol=5e-2)

    def test_stacked_per_channel_falls_back_to_decode(self):
        st = _serve_state(64, 48)
        stk = LutqState(w=None, d=jnp.stack([st.d] * 3),
                        a=jnp.stack([st.a] * 3))
        x = jax.random.normal(jax.random.PRNGKey(4), (4, 64))
        got = ops.lutq_dot(x, stk, backend="fused")  # degrades to decode
        assert got.shape == (3, 4, 48)
        np.testing.assert_allclose(
            np.asarray(got[1]), np.asarray(x @ decode_any(st.d, st.a)),
            rtol=1e-5, atol=1e-5)

    def test_ternary_k3_dictionary(self):
        w = jax.random.normal(jax.random.PRNGKey(5), (64, 40))
        st = init_state(w, QuantSpec(bits=2, constraint="ternary", min_size=1))
        serve = LutqState(w=None, d=st.d, a=st.a)
        x = jax.random.normal(jax.random.PRNGKey(6), (3, 64))
        np.testing.assert_allclose(
            np.asarray(ops.lutq_dot(x, serve, backend="fused")),
            np.asarray(x @ decode_any(st.d, st.a)), rtol=2e-4, atol=2e-4)

    def test_train_form_keeps_ste_gradient(self):
        w = jax.random.normal(jax.random.PRNGKey(7), (32, 16))
        st = init_state(w, QuantSpec(bits=4, min_size=1))
        x = jax.random.normal(jax.random.PRNGKey(8), (4, 32))

        def loss(wm):
            y = ops.lutq_dot(x, LutqState(w=wm, d=st.d, a=st.a),
                             backend="fused")  # train -> decode/STE
            return jnp.sum(y ** 2)

        g = jax.grad(loss)(w)
        # STE: dL/dW == dL/dQ = x^T (2 x Q)
        q = decode_any(st.d, st.a)
        want = x.T @ (2 * (x @ q))
        np.testing.assert_allclose(np.asarray(g), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


class TestResolution:
    def test_auto_rules(self):
        st = _serve_state(64, 48)
        assert ops.resolve_backend(st, "auto") == "fused"
        packed = LutqState(w=None, d=st.d, a=pack4_kin(st.a))
        assert ops.resolve_backend(packed, "auto") == "packed4"
        assert ops.resolve_backend(packed, "auto", transpose_rhs=True) == "decode"
        train = init_state(jax.random.normal(jax.random.PRNGKey(0), (64, 48)),
                           QuantSpec(bits=4, min_size=1))
        assert ops.resolve_backend(train, "fused") == "decode"  # STE
        stacked = LutqState(w=None, d=jnp.stack([st.d] * 2),
                            a=jnp.stack([st.a] * 2))
        assert ops.resolve_backend(stacked, "fused") == "decode"
        assert ops.resolve_backend(stacked, "fused", sliced=True) == "fused"

    def test_explicit_requests_degrade(self):
        st = _serve_state(64, 48)
        # packed4 on an int8 leaf -> fused (no packed layout stored)
        assert ops.resolve_backend(st, "packed4") == "fused"
        assert ops.resolve_backend(st, "decode") == "decode"

    def test_unknown_backend_raises(self):
        st = _serve_state(64, 48)
        with pytest.raises(ValueError, match="unknown backend"):
            ops.resolve_backend(st, "mxu9000")
        with pytest.raises(ValueError):
            ops.lutq_dot(jnp.ones((2, 64)), st, backend="mxu9000")


def _tree():
    k = jax.random.PRNGKey(0)
    return {
        "layers": {
            "attn": {"q": {"kernel": jax.random.normal(k, (64, 64))}},
            "mlp": {"wi": {"kernel": jax.random.normal(k, (64, 128))}},
        },
        "embed": {"table": jax.random.normal(k, (96, 64))},
    }


class TestManifest:
    def test_rule_backend_serialization_roundtrip(self):
        pol = QuantPolicy(rules=(
            QuantRule("*/mlp/*", QuantSpec(bits=4, min_size=1),
                      backend="packed4", name="mlp-p4"),
            QuantRule("*", QuantSpec(bits=4, min_size=1, backend="fused"),
                      name="rest"),
        ), name="be")
        back = QuantPolicy.from_json(pol.to_json())
        assert back == pol
        assert back.rules[0].resolved_backend == "packed4"
        assert back.rules[1].resolved_backend == "fused"  # from the spec

    def test_rule_backend_drives_packing(self):
        pol = QuantPolicy(rules=(
            QuantRule("*/mlp/*", QuantSpec(bits=4, min_size=1),
                      backend="packed4"),
            QuantRule("*", QuantSpec(bits=4, min_size=1), backend="fused"),
        ))
        q = quantize_tree(_tree(), pol)
        sv, man = serve_view(q, policy=pol, with_manifest=True)
        # packed4 rule packs its leaves even without the pack4 flag...
        assert sv["layers"]["mlp"]["wi"]["kernel"].a.dtype == jnp.uint8
        assert man["layers/mlp/wi/kernel"]["backend"] == "packed4"
        # ...and an explicit fused rule keeps int8 even with pack4=True
        sv2 = serve_view(q, pack4=True, policy=pol)
        assert sv2["layers"]["attn"]["q"]["kernel"].a.dtype == jnp.int8

    def test_auto_resolution_roundtrips_through_json(self):
        """backend='auto' resolution recorded by serve_view survives a
        JSON round-trip and matches what lutq_dot resolves per leaf."""
        q = quantize_tree(_tree(), QuantSpec(bits=4, min_size=1))
        sv, man = serve_view(q, pack4=True, with_manifest=True)
        man2 = json.loads(json.dumps(man))
        assert man2 == man
        from repro.nn.tree import tree_paths
        leaves = {"/".join(p): l for p, l in tree_paths(sv)
                  if isinstance(l, LutqState)}
        # "__"-prefixed keys are reserved metadata (e.g. the tuning
        # cache when the process has tuned shapes), not leaf records
        man2 = {k: v for k, v in man2.items() if not k.startswith("__")}
        assert set(man2) == set(leaves)
        for path, rec in man2.items():
            got = ops.resolve_backend(leaves[path], "auto", sliced=True)
            assert got == rec["backend"], path
        # and the standalone manifest of the serve tree agrees
        assert backend_manifest(sv) == man

    def test_manifest_override_matches_forced_dispatch(self):
        q = quantize_tree(_tree(), QuantSpec(bits=4, min_size=1))
        sv = serve_view(q)
        man = backend_manifest(sv, override="decode")
        assert {m["backend"] for m in man.values()} == {"decode"}


ARCHS = ["h2o-danube-1.8b", "mistral-nemo-12b"]


def _serve_setup(arch, **cfg_kw):
    from repro.configs import get_config
    from repro.models import api
    from repro.models.reduce import reduced
    cfg = reduced(get_config(arch)).replace(
        quant=QuantSpec(bits=4, min_size=512), act_bits=32, remat=False,
        **cfg_kw)
    params, axes = api.init(jax.random.PRNGKey(0), cfg)
    q = api.quantize(params, cfg, axes)
    sv = serve_view(q, policy=api.resolved_policy(cfg))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab)
    return cfg, sv, {"tokens": toks}


class TestServeDispatch:
    @pytest.mark.parametrize("arch", ARCHS)
    def test_fused_matches_decode_logits(self, arch):
        from repro.models import api
        cfg, sv, batch = _serve_setup(arch)
        outs = {}
        for be in ("decode", "fused"):
            logits, _ = api.prefill(sv, cfg.replace(kernel_backend=be), batch)
            outs[be] = np.asarray(logits, np.float32)
        np.testing.assert_allclose(outs["fused"], outs["decode"],
                                   rtol=2e-3, atol=2e-3)

    @pytest.mark.slow
    def test_packed4_serve_tree_matches_decode(self):
        from repro.configs import get_config
        from repro.models import api
        from repro.models.reduce import reduced
        cfg = reduced(get_config("mistral-nemo-12b")).replace(
            quant=QuantSpec(bits=4, min_size=512), act_bits=32, remat=False)
        params, axes = api.init(jax.random.PRNGKey(0), cfg)
        q = api.quantize(params, cfg, axes)
        sv = serve_view(q, pack4=True, policy=api.resolved_policy(cfg))
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab)
        outs = {}
        for be in ("decode", "auto"):
            logits, _ = api.prefill(sv, cfg.replace(kernel_backend=be),
                                    {"tokens": toks})
            outs[be] = np.asarray(logits, np.float32)
        np.testing.assert_allclose(outs["auto"], outs["decode"],
                                   rtol=2e-3, atol=2e-3)

    @pytest.mark.slow
    def test_no_dense_materialize_on_fused_path(self, monkeypatch):
        """Acceptance: in serve mode with the fused backend, no matmul
        leaf decodes a dense weight matrix — only gather-style uses
        (the embedding lookup) may."""
        import repro.kernels.ops as ops_mod
        import repro.nn.linear as lin_mod

        calls = []
        real = decode_any

        def counting(d, a):
            calls.append(d.shape)
            return real(d, a)

        monkeypatch.setattr(lin_mod, "decode_any", counting)
        monkeypatch.setattr(ops_mod, "decode_any", counting)
        from repro.models import api
        cfg, sv, batch = _serve_setup("mistral-nemo-12b")

        calls.clear()
        api.prefill(sv, cfg.replace(kernel_backend="fused"), batch)
        fused_calls = len(calls)
        calls.clear()
        api.prefill(sv, cfg.replace(kernel_backend="decode"), batch)
        decode_calls = len(calls)
        # fused path: exactly the embedding gather; decode path: every
        # projection decodes densely.
        assert fused_calls == 1, fused_calls
        assert decode_calls > fused_calls

    def test_meshed_dots_keep_their_recorded_sharding(self, monkeypatch):
        """Scan slices the layer axis off a stacked leaf but not off its
        serve spec: a row-parallel projection (spec ``P(None, "model")``
        on its (L, K, N) stack) must reach the shard_map sharded on K,
        not on N, and a column-parallel one on N."""
        from jax.sharding import PartitionSpec as P

        from repro.launch.mesh import make_host_mesh
        from repro.runtime.serving import prefill_fn

        specs = {}
        real = ops.lutq_dot_spmd

        def spy(x, state, mesh, **kw):
            specs[state.a.shape] = kw["a_spec"]
            return real(x, state, mesh, **kw)

        monkeypatch.setattr(ops, "lutq_dot_spmd", spy)
        cfg, sv, batch = _serve_setup("h2o-danube-1.8b",
                                      kernel_backend="fused")
        prefill_fn(cfg, batch["tokens"].shape[1], make_host_mesh(1, 1))(
            sv, batch)

        def spec(*path):
            leaf = sv["layers"]
            for p in path:
                leaf = leaf[p]
            return specs[leaf["kernel"].a.shape[1:]]

        # (d_ff differs from d_model, so these two shapes are distinct)
        assert spec("mlp", "wo") == P("model", None)
        assert spec("mlp", "wi") == P(None, "model")

    def test_generate_backend_kwarg_and_stats(self):
        from repro.runtime.serving import decode_fn, generate
        cfg, sv, batch = _serve_setup("h2o-danube-1.8b")
        out_d = generate(sv, cfg, batch, steps=4, backend="decode")
        out_f, stats = generate(sv, cfg, batch, steps=4, backend="fused",
                                return_stats=True)
        np.testing.assert_array_equal(np.asarray(out_d), np.asarray(out_f))
        assert stats["backend"] == "fused" and stats["decode_tok_s"] > 0
        # decode jit is cached per config (no per-call re-wrap)
        c = cfg.replace(kernel_backend="fused")
        assert decode_fn(c) is decode_fn(c)
