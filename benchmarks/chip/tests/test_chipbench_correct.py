"""``correct`` is decided by a comparison that can fail: a whole serve run
of a small model on the CPU (kernels in interpret mode) is correct as it
stands, its float8 control is not, and neither is a run whose timed path
is broken underneath: a token altered where it is produced, or a decode
step that returns its state unchanged.

The limit here is the small model's own: sound runs of it read gaps of
0 to 0.01, its control 0.075 to 0.15 (three seeds each, on the CPU).
The cell's limit, at its own size, lives in its traffic file.
"""
import json
import sys

import jax.numpy as jnp
import pytest

from chipbench import harness

sys.path.insert(0, str(harness.HERE))
import run  # noqa: E402

SMALL_LIMIT = 0.03


def _ctx(seed, control=False):
    conf = harness.load_json(harness.HERE / "configs" / "h2o-danube-1.8b.json")
    conf.update(hidden_size=256, intermediate_size=512, num_hidden_layers=2,
                num_attention_heads=4, num_key_value_heads=2,
                vocab_size=512, sliding_window=64)
    tf = harness.load_json(harness.traffic_file("chat"))
    tf.update(rate_rps=4.0, warm_s=1, drain_max_s=20, slots=4, max_len=96,
              pool_tokens=512, max_chunk=32, check_tokens=40,
              max_gap_limit=SMALL_LIMIT,
              prompt={"dist": "lognormal", "median": 24, "sigma": 0.5,
                      "min": 8, "max": 64},
              max_new={"dist": "lognormal", "median": 8, "sigma": 0.5,
                       "min": 4, "max": 32})
    args = run.parse(["--workload", "danube-chat", "--seed", str(seed),
                      "--seconds", "2", "--trace", "0"])
    ctx = run.prepare(args, allow_cpu=True, conf=conf, tf=tf)
    ctx["control"] = control
    return ctx


@pytest.fixture
def fresh_traces():
    """Drop the engine's cached jits around a test, so a patched function
    is traced anew and no later test sees it."""
    from repro.runtime import engine

    def clear():
        engine._paged_step_fn_cached.cache_clear()
        engine._sample_fn.cache_clear()

    clear()
    yield
    clear()


def test_sound_run_is_correct_and_its_control_is_not(fresh_traces):
    ctx = _ctx(21)
    res, checks = ctx["kind"].run(ctx)
    assert res["correct"], checks
    assert checks["max_gap"]["value"] <= SMALL_LIMIT
    assert checks["token_count_mismatch"]["value"] == 0
    want = {k for k, _ in harness.cell_metrics(ctx["bench"], "danube-chat",
                                               False)}
    assert set(res["metrics"]) == want
    assert res["attempted"] > 0 and res["failed"] == 0
    json.dumps(res)
    # the same run with the control in the program's place
    ctx = _ctx(21, control=True)
    res, checks = ctx["kind"].run(ctx)
    assert not res["correct"]
    assert checks["max_gap"]["value"] > SMALL_LIMIT


def test_altered_token_is_incorrect(fresh_traces, monkeypatch):
    from repro.runtime import engine

    orig = engine._sample

    def altered(logits, keys, temp, greedy):
        tok, keys = orig(logits, keys, temp, greedy)
        return (tok + 1) % logits.shape[-1], keys

    monkeypatch.setattr(engine, "_sample", altered)
    ctx = _ctx(22)
    res, checks = ctx["kind"].run(ctx)
    assert not res["correct"]
    assert checks["max_gap"]["value"] > SMALL_LIMIT


def test_step_returning_its_state_unchanged_is_incorrect(fresh_traces,
                                                          monkeypatch):
    from repro.models import api

    orig = api.paged_decode_step

    def stale(params, cfg, token, cache, mesh=None):
        logits, _ = orig(params, cfg, token, cache, mesh=mesh)
        return logits, cache

    monkeypatch.setattr(api, "paged_decode_step", stale)
    ctx = _ctx(23)
    res, checks = ctx["kind"].run(ctx)
    assert not res["correct"]
    assert checks["max_gap"]["value"] > SMALL_LIMIT
