"""Tests for optim / data / checkpoint / runtime / distributed substrates."""
import os
import signal
import subprocess
import sys
import tempfile
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.checkpoint.ckpt import AsyncCheckpointer, latest_step, restore, save
from repro.core.lutq import LutqState
from repro.core.spec import QuantSpec
from repro.data.loader import Prefetcher
from repro.data.synthetic import MarkovLM, shapes_dataset
from repro.data.text import byte_batch, default_corpus
from repro.distributed.compress import ef_int8_transform, init_ef_state
from repro.optim.optimizers import adamw, clip_by_global_norm, cosine_schedule, sgd


class TestOptimizers:
    def _rosenbrock_ish(self):
        target = jnp.asarray([1.0, -2.0, 3.0])

        def loss(p):
            return jnp.sum((p["w"] - target) ** 2) + 0.1 * jnp.sum(p["w"] ** 4)

        return loss

    @pytest.mark.parametrize("opt", [sgd(0.02, momentum=0.9), adamw(0.1)])
    def test_converges_to_stationary_point(self, opt):
        loss = self._rosenbrock_ish()
        params = {"w": jnp.zeros(3), "skip": None}
        state = opt.init(params)
        step = jnp.zeros((), jnp.int32)
        for i in range(300):
            g = jax.grad(loss)(params)
            params, state = opt.update(g, state, params, step + i)
        gnorm = float(jnp.linalg.norm(jax.grad(loss)(params)["w"]))
        # constant-lr Adam hovers near the minimum; 0.05 is well below the
        # O(5) gradient magnitudes away from the basin
        assert gnorm < 5e-2, gnorm

    def test_none_leaves_pass_through(self):
        opt = adamw(0.1)
        params = {"a": jnp.ones(2), "b": None}
        st_ = opt.init(params)
        g = {"a": jnp.ones(2), "b": None}
        p2, _ = opt.update(g, st_, params, jnp.zeros((), jnp.int32))
        assert p2["b"] is None and not jnp.allclose(p2["a"], params["a"])

    def test_clip_by_global_norm(self):
        g = {"a": jnp.ones(4) * 10, "b": None}
        clipped, gn = clip_by_global_norm(g, 1.0)
        assert abs(float(gn) - 20.0) < 1e-4
        norm2 = jnp.sqrt(jnp.sum(clipped["a"] ** 2))
        assert abs(float(norm2) - 1.0) < 1e-4

    def test_cosine_schedule(self):
        sch = cosine_schedule(1.0, warmup=10, total=100, floor=0.1)
        assert float(sch(jnp.asarray(0))) == 0.0
        assert abs(float(sch(jnp.asarray(10))) - 1.0) < 1e-6
        assert abs(float(sch(jnp.asarray(100))) - 0.1) < 1e-6

    def test_weight_decay_direction(self):
        opt = adamw(0.1, weight_decay=0.5)
        params = {"w": jnp.ones(1) * 4.0}
        st_ = opt.init(params)
        g = {"w": jnp.zeros(1)}
        p2, _ = opt.update(g, st_, params, jnp.zeros((), jnp.int32))
        assert float(p2["w"][0]) < 4.0


class TestCheckpoint:
    def _tree(self):
        return {
            "layer": {"kernel": LutqState(w=jnp.ones((4, 4)),
                                          d=jnp.asarray([0.0, 1.0]),
                                          a=jnp.zeros((4, 4), jnp.int8)),
                      "bias": jnp.arange(4.0)},
            "step": jnp.asarray(7, jnp.int32),
            "missing": None,
        }

    def test_roundtrip(self):
        with tempfile.TemporaryDirectory() as td:
            save(self._tree(), td, 7)
            tree, step = restore(td)
            assert step == 7
            assert isinstance(tree["layer"]["kernel"], LutqState)
            np.testing.assert_array_equal(tree["layer"]["bias"], np.arange(4.0))
            assert tree["missing"] is None
            assert tree["layer"]["kernel"].a.dtype == np.int8

    def test_keep_n_gc(self):
        with tempfile.TemporaryDirectory() as td:
            for s in range(6):
                save({"x": jnp.asarray(s)}, td, s, keep_n=2)
            assert latest_step(td) == 5
            tree, _ = restore(td, 5)
            steps = sorted(os.listdir(td))
            assert len([s for s in steps if not s.endswith(".tmp")]) == 2

    def test_atomicity_partial_invisible(self):
        with tempfile.TemporaryDirectory() as td:
            save({"x": jnp.asarray(1)}, td, 1)
            # a stale tmp dir from a crashed writer must be ignored
            os.makedirs(os.path.join(td, "step_00000009.tmp"))
            assert latest_step(td) == 1

    def test_async_checkpointer(self):
        with tempfile.TemporaryDirectory() as td:
            ck = AsyncCheckpointer(td)
            ck.save(self._tree(), 3)
            ck.wait()
            assert latest_step(td) == 3

    def test_elastic_restore_resharding(self):
        """Restore places arrays with provided shardings (device_put)."""
        with tempfile.TemporaryDirectory() as td:
            save({"w": jnp.arange(8.0)}, td, 1)
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
            mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
            tree, _ = restore(td, shardings={"w": NamedSharding(mesh, P())})
            np.testing.assert_array_equal(np.asarray(tree["w"]), np.arange(8.0))


class TestData:
    def test_markov_deterministic_and_learnable(self):
        lm = MarkovLM(64, seed=3)
        b1 = lm.batch(0, step=5, batch_size=4, seq_len=16)
        b2 = lm.batch(0, step=5, batch_size=4, seq_len=16)
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
        assert lm.entropy_floor() < np.log(64) / 2

    def test_host_sharding_partitions(self):
        lm = MarkovLM(64, seed=3)
        full = lm.batch(0, step=2, batch_size=8, seq_len=8)
        h0 = lm.batch(0, step=2, batch_size=8, seq_len=8, host_id=0, num_hosts=2)
        h1 = lm.batch(0, step=2, batch_size=8, seq_len=8, host_id=1, num_hosts=2)
        np.testing.assert_array_equal(
            np.concatenate([h0["tokens"], h1["tokens"]]), full["tokens"])

    def test_byte_corpus(self):
        corpus = default_corpus(os.path.dirname(os.path.dirname(__file__)))
        b = byte_batch(corpus, step=3, batch_size=4, seq_len=32)
        assert b["tokens"].shape == (4, 32)
        np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])

    def test_shapes_dataset_classes(self):
        xs, ys = shapes_dataset(64, seed=0)
        assert xs.shape == (64, 16, 16, 3) and set(np.unique(ys)) <= set(range(8))

    def test_prefetcher_deterministic_order(self):
        pf = Prefetcher(lambda s: {"step": np.asarray(s)}, start_step=10, depth=2)
        got = [next(pf)[0] for _ in range(5)]
        pf.close()
        assert got == [10, 11, 12, 13, 14]


class TestCompression:
    def test_ef_int8_unbiased_over_time(self):
        """Error feedback: sum of compressed grads -> sum of true grads."""
        g = jax.random.normal(jax.random.PRNGKey(0), (128,))
        ef = init_ef_state({"g": g})
        total = jnp.zeros_like(g)
        for i in range(50):
            out, ef = ef_int8_transform({"g": g}, ef)
            total = total + out["g"]
        np.testing.assert_allclose(np.asarray(total / 50), np.asarray(g),
                                   atol=1e-2)

    def test_ef_residual_bounded(self):
        g = jax.random.normal(jax.random.PRNGKey(1), (64,)) * 10
        ef = init_ef_state({"g": g})
        for _ in range(20):
            _, ef = ef_int8_transform({"g": g}, ef)
        scale = float(jnp.max(jnp.abs(g))) / 127
        assert float(jnp.max(jnp.abs(ef["g"]))) <= scale * 2

    @given(st.integers(0, 100))
    @settings(max_examples=10, deadline=None)
    def test_property_compression_error_small(self, seed):
        g = jax.random.normal(jax.random.PRNGKey(seed), (256,))
        ef = init_ef_state({"g": g})
        out, _ = ef_int8_transform({"g": g}, ef)
        err = jnp.max(jnp.abs(out["g"] - g))
        assert float(err) <= float(jnp.max(jnp.abs(g))) / 127 + 1e-6


RING_TEST = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    import sys; sys.path.insert(0, "src")
    from repro.distributed.compress import ring_allreduce

    mesh = jax.make_mesh((8,), ("data",))
    x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)

    f = jax.shard_map(lambda s: ring_allreduce(s, "data"), mesh=mesh,
                  in_specs=P("data", None), out_specs=P("data", None))
    out = jax.jit(f)(x)
    expect = jnp.broadcast_to(x.reshape(8, 1, 8).sum(0), (8, 8))
    # each shard holds the full sum of its slice pattern
    ref = jnp.tile(x.reshape(8, 8).sum(0, keepdims=True), (8, 1))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5)
    print("RING_OK")
""")


class TestRingAllreduce:
    def test_ring_on_8_host_devices(self):
        """Runs in a subprocess so the 8-device XLA flag doesn't leak."""
        r = subprocess.run([sys.executable, "-c", RING_TEST],
                           capture_output=True, text=True,
                           cwd=os.path.dirname(os.path.dirname(__file__)))
        assert "RING_OK" in r.stdout, r.stdout + r.stderr


class TestLoop:
    def test_watchdog_flags_stragglers(self):
        from repro.runtime.loop import StragglerWatchdog
        wd = StragglerWatchdog(factor=3.0)
        for _ in range(10):
            wd.observe(0.01)
        assert wd.observe(0.05) and wd.flagged == 1
        assert not wd.observe(0.011)

    def test_loop_resume_continues_step_count(self):
        from repro.runtime.loop import TrainLoop

        def step_fn(state, batch):
            return {"x": state["x"] + 1}, {"loss": jnp.asarray(1.0)}

        with tempfile.TemporaryDirectory() as td:
            loop = TrainLoop(step_fn, lambda s: {}, ckpt_dir=td, ckpt_every=5,
                             log_every=1000)
            state, step = loop.run({"x": jnp.asarray(0)}, 7, handle_signals=False)
            assert step == 7 and int(state["x"]) == 7
            loop2 = TrainLoop(step_fn, lambda s: {}, ckpt_dir=td,
                              ckpt_every=100, log_every=1000)
            state2, step2 = loop2.run({"x": jnp.asarray(0)}, 10,
                                      handle_signals=False)
            assert step2 == 10 and int(state2["x"]) == 10  # resumed from 7
