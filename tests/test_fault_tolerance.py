"""Fault-tolerance integration tests, each in a subprocess with its own
device topology: elastic re-mesh restore (4 -> 8 devices) and SIGTERM
preemption checkpointing."""
import os
import signal
import subprocess
import sys
import tempfile
import textwrap
import time

ROOT = os.path.dirname(os.path.dirname(__file__))


def _run(code, timeout=300, env_extra=None):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    if env_extra:
        env.update(env_extra)
    return subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=timeout, env=env, cwd=ROOT)


ELASTIC_PHASE1 = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys; sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.checkpoint.ckpt import save
    mesh = jax.make_mesh((4,), ("data",))
    w = jax.device_put(jnp.arange(32.0).reshape(8, 4),
                       NamedSharding(mesh, P("data", None)))
    # one "training" update on the 4-device mesh
    w = jax.jit(lambda w: w * 2 + 1)(w)
    save({"w": w, "step": jnp.asarray(3)}, sys.argv_dir, 3)
    print("PHASE1_OK")
""")

ELASTIC_PHASE2 = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys; sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.checkpoint.ckpt import restore
    mesh = jax.make_mesh((8,), ("data",))
    sh = {"w": NamedSharding(mesh, P("data", None)), "step": None}
    tree, step = restore(sys.argv_dir, shardings=sh)
    assert step == 3
    w = tree["w"]
    assert len(w.sharding.device_set) == 8, w.sharding
    expect = np.arange(32.0).reshape(8, 4) * 2 + 1
    np.testing.assert_array_equal(np.asarray(w), expect)
    # keep training on the NEW mesh
    w2 = jax.jit(lambda w: w + 1)(w)
    np.testing.assert_array_equal(np.asarray(w2), expect + 1)
    print("PHASE2_OK")
""")


class TestElasticRemesh:
    def test_restore_onto_larger_mesh(self):
        with tempfile.TemporaryDirectory() as td:
            p1 = ELASTIC_PHASE1.replace("sys.argv_dir", repr(td))
            r1 = _run(p1)
            assert "PHASE1_OK" in r1.stdout, r1.stdout + r1.stderr
            p2 = ELASTIC_PHASE2.replace("sys.argv_dir", repr(td))
            r2 = _run(p2)
            assert "PHASE2_OK" in r2.stdout, r2.stdout + r2.stderr


PREEMPT = textwrap.dedent("""
    import os, sys, signal, threading
    sys.path.insert(0, "src")
    import jax.numpy as jnp
    from repro.runtime.loop import TrainLoop

    def slow_step(state, batch):
        import time; time.sleep(0.05)
        return {"x": state["x"] + 1}, {"loss": jnp.asarray(1.0)}

    loop = TrainLoop(slow_step, lambda s: {}, ckpt_dir=sys.argv_dir,
                     ckpt_every=10_000, log_every=10_000)
    # deliver SIGTERM to ourselves mid-run
    threading.Timer(0.4, lambda: os.kill(os.getpid(), signal.SIGTERM)).start()
    state, step = loop.run({"x": jnp.asarray(0)}, 10_000)
    assert step < 10_000, "should have been preempted"
    from repro.checkpoint.ckpt import latest_step
    assert latest_step(sys.argv_dir) == step
    print("PREEMPT_OK", step)
""")


class TestPreemption:
    def test_sigterm_checkpoints_and_exits(self):
        with tempfile.TemporaryDirectory() as td:
            r = _run(PREEMPT.replace("sys.argv_dir", repr(td)), timeout=120)
            assert "PREEMPT_OK" in r.stdout, r.stdout + r.stderr
