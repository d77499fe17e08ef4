"""Integration tests for the launch layer: sharding rules, partition
specs, and a real (subprocess) dry-run cell on the 512-device mesh."""
import json
import os
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.distributed.sharding import LOGICAL_RULES, pspec_for
from repro.models.api import SHAPES, input_specs, supports_shape
from repro.configs import get_config, list_archs

ROOT = os.path.dirname(os.path.dirname(__file__))


def _mesh():
    dev = np.array(jax.devices()[:1]).reshape(1, 1)
    return Mesh(dev, ("data", "model"))


class TestPspecRules:
    def test_no_duplicate_axes(self):
        mesh = _mesh()
        # expert + moe_mlp must not both claim "model"
        spec = pspec_for(("expert", "embed", "moe_mlp"), mesh, (128, 64, 64))
        flat = [a for part in spec if part for a in
                (part if isinstance(part, tuple) else (part,))]
        assert len(flat) == len(set(flat))

    def test_divisibility_fallback(self):
        # stub 16x16 mesh (pspec_for only reads axis_names + shape)
        class M:
            axis_names = ("data", "model")
            shape = {"data": 16, "model": 16}
        # 8 kv-heads don't divide a 16-way model axis -> replicated
        spec = pspec_for(("kv_heads",), M(), (8,))
        assert spec in (P(None), P())
        # 64 heads do
        assert pspec_for(("heads",), M(), (64,)) == P("model")

    def test_vocab_in_unsharded(self):
        assert LOGICAL_RULES["vocab_in"] == ()
        assert LOGICAL_RULES["kv_lora"] == ()


class TestInputSpecs:
    @pytest.mark.parametrize("arch", list_archs())
    @pytest.mark.parametrize("shape_name", list(SHAPES))
    def test_specs_are_shape_structs(self, arch, shape_name):
        cfg = get_config(arch)
        shape = SHAPES[shape_name]
        ok, _ = supports_shape(cfg, shape)
        if not ok:
            pytest.skip("assignment-prescribed skip")
        specs = input_specs(cfg, shape)
        leaves = jax.tree.leaves(specs)
        assert leaves and all(isinstance(l, jax.ShapeDtypeStruct) for l in leaves)
        if shape.kind == "train":
            toks = specs.get("tokens")
            total = toks.shape[0] * (toks.shape[1] + cfg.n_prefix_tokens
                                     if cfg.family == "vlm" else toks.shape[1])
            assert toks.shape[0] == shape.global_batch
        if shape.kind == "decode":
            assert specs["token"].shape == (shape.global_batch, 1)

    def test_long_500k_skips_match_design(self):
        skips = {a for a in list_archs()
                 if not supports_shape(get_config(a), SHAPES["long_500k"])[0]}
        assert skips == {"qwen1.5-110b", "mistral-nemo-12b",
                         "mistral-large-123b", "paligemma-3b",
                         "qwen3-moe-235b-a22b", "seamless-m4t-medium"}


DRYRUN_ONE = f"""
import sys
sys.path.insert(0, {ROOT + "/src"!r})
from repro.launch.dryrun import run_cell
from pathlib import Path
import tempfile, json
with tempfile.TemporaryDirectory() as td:
    rec = run_cell("rwkv6-1.6b", "long_500k", multi_pod=True,
                   out_dir=Path(td))
    assert rec["status"] == "ok", rec
    assert rec["devices"] == 512
    assert rec["memory"]["temp_bytes"] > 0
    print("DRYRUN_OK", rec["compile_s"])
"""


class TestDryrunCell:
    @pytest.mark.slow
    def test_one_cell_on_512_devices(self):
        """Full lower+compile of one cell on the 2x16x16 mesh, in a
        subprocess so the 512-device XLA flag doesn't leak here."""
        r = subprocess.run([sys.executable, "-c", DRYRUN_ONE],
                           capture_output=True, text=True, timeout=420)
        assert "DRYRUN_OK" in r.stdout, r.stdout + r.stderr


class TestArtifacts:
    """The checked-in dry-run artifacts must be complete and green."""

    ART = os.path.join(ROOT, "benchmarks/artifacts/dryrun")

    @pytest.mark.parametrize("mesh", ["pod16x16", "pod2x16x16"])
    def test_sweep_complete_and_green(self, mesh):
        d = os.path.join(self.ART, mesh)
        if not os.path.isdir(d):
            pytest.skip("dry-run artifacts not generated")
        seen = ok = 0
        for arch in list_archs():
            for shape in SHAPES:
                f = os.path.join(d, f"{arch}__{shape}.json")
                assert os.path.exists(f), f"missing cell {arch}/{shape}"
                rec = json.load(open(f))
                seen += 1
                assert rec["status"] in ("ok", "skipped"), (
                    arch, shape, rec.get("error"))
                if rec["status"] == "ok":
                    ok += 1
                    assert rec["cost"]["flops"] > 0
        assert seen == 40 and ok >= 33


class TestHostMesh:
    def test_refuses_a_mesh_larger_than_the_host(self):
        """A mesh is never shrunk to the devices present: that would run
        (and report) a different layout than the one asked for."""
        from repro.launch.mesh import make_host_mesh

        n = len(jax.devices())
        with pytest.raises(ValueError, match="needs"):
            make_host_mesh(1, n + 1)
        mesh = make_host_mesh(1, n)
        assert dict(mesh.shape) == {"data": 1, "model": n}
