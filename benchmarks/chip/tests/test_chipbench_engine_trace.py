"""The program's own step records against what the benchmark reads.

A small paged engine on the CPU serves chunked, packed and prefix-hit
admissions through ``serve.Stepper``. Step by step, the engine's record
of its prefill and admissions equals what ``Stepper`` reads by wrapping
the engine's prefill methods, and each reader of the program's step
records returns a finite number on that run; on a program without the
recorder they return nothing and do not raise.
"""
import math
import sys

import numpy as np
import pytest

from chipbench import harness, serve

if str(harness.ROOT / "src") not in sys.path:
    sys.path.insert(0, str(harness.ROOT / "src"))

READERS = ("engine_host_ms.chat", "engine_host_ms.docqa",
           "prefill_pad_share.chat", "compiles_in_steps.chat",
           "compiles_in_steps.docqa")


def _read(name, ctx):
    mod = harness.load_module(harness.metric_file(name),
                              "chipbench_metric_" + name.replace(".", "_"))
    return mod.read(ctx)


@pytest.fixture(scope="module")
def driven():
    import jax

    from repro.configs import get_config
    from repro.models import api
    from repro.models.reduce import reduced
    from repro.runtime import tracing
    from repro.runtime.engine import Engine

    cfg = reduced(get_config("h2o-danube-1.8b")).replace(
        quant=None, act_bits=32, remat=False)
    params, _ = api.init(jax.random.PRNGKey(0), cfg)
    eng = Engine(params, cfg, capacity=3, max_len=48, kv_pages=24,
                 page_size=8, max_chunk=16, rng=jax.random.PRNGKey(0))
    drv = serve.Stepper(eng)
    rng = np.random.default_rng(1)
    shared = rng.integers(0, cfg.vocab, 20)
    tracing.RECORDER.clear()
    for L in (30, 6, 9, 25, 7, 5, 28):
        p = (np.concatenate([shared, rng.integers(0, cfg.vocab, L - 20)])
             if L > 20 else rng.integers(0, cfg.vocab, L))
        drv.submit(serve.Rec("window", 0.0, p.astype(np.int32), 4), 0.0)
    while not eng.idle:
        drv.step()
    return drv, tracing.RECORDER.records("engine.step")


def test_step_records_equal_the_wrapped_methods(driven):
    drv, recs = driven
    assert len(recs) == len(drv.steps)
    kinds = set()
    for step, rec in zip(drv.steps, recs):
        assert step["t0"] * 1e9 <= rec.t0 <= rec.t1 <= step["t1"] * 1e9
        assert [(s, n) for s, n, _ in rec.notes.get("prefill", ())] == \
            step["prefill"]
        assert rec.notes.get("admitted", []) == step["admitted"]
        kinds |= {c.name for c in rec.children}
        kinds |= {s.name for c in rec.children for s in c.children}
    # the run held chunked and packed prefill and a prefix hit
    assert {"engine.prefill_chunk", "engine.prefill_packed"} <= kinds
    assert any(h for s in drv.steps for _, h in s["admitted"])


def test_readers_return_finite_numbers(driven):
    drv, _ = driven
    ctx = {"steps": drv.steps}
    vals = {name: _read(name, ctx) for name in READERS}
    assert all(v is not None and math.isfinite(v) for v in vals.values()), \
        vals
    assert vals["engine_host_ms.chat"] > 0
    assert 0 < vals["prefill_pad_share.chat"] < 100
    # a span of only the first step reads that step alone
    one = {"steps": drv.steps[:1]}
    assert _read("engine_host_ms.chat", one) > 0


def test_readers_without_the_recorder_return_nothing(driven, monkeypatch):
    drv, _ = driven
    for name in READERS:
        assert _read(name, {"steps": []}) is None
    # a program that lacks runtime/tracing.py
    import repro.runtime

    monkeypatch.delattr(repro.runtime, "tracing")
    monkeypatch.setitem(sys.modules, "repro.runtime.tracing", None)
    for name in READERS:
        assert _read(name, {"steps": drv.steps}) is None
