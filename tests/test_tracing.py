"""The flight recorder (runtime/tracing.py) and the paged engine's spans
and counters.

The recorder's own rules run on a fixed clock: nesting, parents, self
time, the bounded ring, the off switch and compiles landing under the
span that caused them. The engine case serves a small mixed workload
(chunked, packed and prefix-hit admissions) on the reduced config that
``test_paged_kv.py`` uses, and checks that the step records count the
prompt tokens it computed, that ``stats()`` reads its times from the
spans, and that serving compiles none of the engine's warmed jits.
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import api
from repro.models.reduce import reduced
from repro.runtime import engine, tracing
from repro.runtime.engine import Engine

# the keys Engine.stats() had before it read the recorder
PAGED_STATS_KEYS = {
    "capacity", "max_len", "backend", "mesh", "admitted", "completed",
    "decode_steps", "new_tokens", "t_prefill_s", "t_decode_s", "wall_s",
    "decode_tok_s", "goodput_tok_s", "tokens_per_engine_step",
    "p50_latency_s", "p95_latency_s", "ttft_p50_s", "ttft_p99_s", "paged",
    "kv_bytes_per_token", "t_warmup_s", "prefill_chunk_calls",
    "packed_groups", "packed_requests"}
ENGINE_JITS = ("paged_decode_step", "paged_prefill_chunk",
               "paged_prefill_packed", "paged_hydrate", "lm_paged_splice",
               "_sample")


def _ticking(step=10):
    ticks = itertools.count(0, step)
    return lambda: next(ticks)


def test_nesting_parent_and_self_time_on_a_fixed_clock():
    rec = tracing.Recorder(clock=_ticking())
    own = tracing.Totals()
    with rec.span("a", totals=own) as a:          # 0 .. 50
        with rec.span("b", rid=4, start=1) as b:  # 10 .. 20
            rec.count("x", 2)
            rec.note("p", (1, 2, 3))
        with rec.span("c") as c:                  # 30 .. 40
            pass
    assert (a.ns, b.ns, c.ns) == (50, 10, 10)
    assert a.self_ns == 30 and b.self_ns == 10
    assert a.parent is None and b.parent is a and c.parent is a
    assert a.children == [b, c] and [s.name for s in a.walk()] == \
        ["a", "b", "c"]
    assert (b.rid, b.attrs, a.rid) == (4, {"start": 1}, -1)
    assert list(rec.roots) == [a]
    # counters and notes land on the open root, in the tree's totals and
    # in the process totals
    assert a.counts == {"x": 2} and a.notes == {"p": [(1, 2, 3)]}
    assert own.ns == {"a": 50, "b": 10, "c": 10} and own.counts == {"x": 2}
    assert rec.totals.ns == own.ns and own.seconds("b", "c") == 20e-9
    # outside every span a count reaches the process totals alone
    rec.count("x")
    assert own.counts == {"x": 2} and rec.totals.counts == {"x": 3}


def test_ring_stays_bounded():
    rec = tracing.Recorder(capacity=3, clock=_ticking())
    for i in range(5):
        with rec.span("engine.step", rid=i):
            pass
    rec.add("request.queue", 100, 200, rid=9)
    assert [r.rid for r in rec.roots] == [3, 4, 9]
    assert [r.rid for r in rec.records("engine.step")] == [3, 4]
    assert [r.rid for r in rec.records("engine.step", lo_ns=75)] == [4]
    # the totals are cumulative: the ring forgets, they do not
    assert rec.totals.ns["engine.step"] == 50
    rec.clear()
    assert not rec.roots and not rec.totals.ns


def test_disabled_records_nothing():
    rec = tracing.Recorder(clock=_ticking())
    rec.enabled = False
    own = tracing.Totals()
    first = rec.span("engine.step", totals=own)
    assert rec.span("engine.admit") is first  # one shared null context
    with first:
        rec.count("syncs")
        rec.note("prefill", (0, 1, 1))
    rec.add("request.queue", 0, 1)
    assert not rec.roots and not rec.totals.ns and not rec.totals.counts
    assert not own.ns and not own.counts


def test_forced_compile_is_a_child_of_its_span():
    rec = tracing.RECORDER
    rec.clear()
    x = jnp.arange(7.0)
    with rec.span("outer") as outer:
        with rec.span("inner") as inner:
            jax.jit(lambda v: v * 3.0 - 1.0)(x).block_until_ready()
    comp = [c for c in inner.children if c.name == "compile"]
    assert comp and comp[0].parent is inner
    assert "lambda" in comp[0].attrs["fun"]
    assert inner.t0 <= comp[0].t0 <= comp[0].t1 <= inner.t1
    assert outer.counts["compiles"] >= 1
    assert not [c for c in outer.children if c.name == "compile"]


# ---------------------------------------------------------------------------
# the paged engine
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served():
    """A reduced danube engine (GQA + sliding window) that serves six
    requests: two sharing a 20-token prefix, long ones chunked in 16s,
    short ones packed. Returns the engine, the prompts and the kept
    root spans of the serve."""
    cfg = reduced(get_config("h2o-danube-1.8b")).replace(
        quant=None, act_bits=32, remat=False)
    params, _ = api.init(jax.random.PRNGKey(0), cfg)
    eng = Engine(params, cfg, capacity=3, max_len=48, kv_pages=24,
                 page_size=8, max_chunk=16)
    traces = eng.paged_trace_counts()
    rng = np.random.default_rng(0)
    shared = rng.integers(0, cfg.vocab, 20)
    prompts = []
    for L in (30, 6, 9, 25, 7, 5):
        tail = rng.integers(0, cfg.vocab, L - 20 if L > 20 else L)
        prompts.append(np.concatenate([shared, tail]) if L > 20 else tail)
    tracing.RECORDER.clear()
    for p in prompts:
        eng.submit(p.astype(np.int32), max_new=4)
    res = eng.run()
    assert eng.paged_trace_counts() == traces
    return eng, prompts, res, list(tracing.RECORDER.roots)


def test_step_records_count_prompt_tokens_less_hits(served):
    eng, prompts, _, roots = served
    steps = [r for r in roots if r.name == "engine.step"]
    decoded = [r for r in steps
               if any(c.name == "engine.decode" for c in r.children)]
    assert len(decoded) == eng.n_decode_steps
    prefill = [e for r in steps for e in r.notes.get("prefill", ())]
    admitted = [e for r in steps for e in r.notes.get("admitted", ())]
    assert sorted(L for L, _ in admitted) == sorted(len(p) for p in prompts)
    hits = sum(h for _, h in admitted)
    assert hits > 0                          # the shared prefix hit
    assert sum(n for _, n, _ in prefill) == \
        sum(len(p) for p in prompts) - hits
    assert all(0 < n <= w for _, n, w in prefill)
    assert eng.n_packed_groups > 0 and len(prefill) > eng.n_chunk_calls
    st = eng.stats()
    assert st["prefill_tokens_real"] == sum(n for _, n, _ in prefill)
    assert st["prefill_tokens_padded"] == sum(w - n for _, n, w in prefill)
    assert st["admitted_prompt_tokens"] == sum(len(p) for p in prompts)
    assert st["prefix_hit_tokens"] == hits
    assert st["decode_rows"] == sum(r.counts.get("decode_rows", 0)
                                    for r in steps)


def test_stats_keep_their_keys_and_read_the_spans(served):
    eng, _, _, roots = served
    st = eng.stats()
    assert set(st) == (PAGED_STATS_KEYS | set(eng.pkv.stats())
                       | set(engine.COUNTERS))
    admit = sum(s.ns for r in roots if r.name == "engine.step"
                for s in r.walk() if s.name == "engine.admit")
    decode = sum(s.ns for r in roots if r.name == "engine.step"
                 for s in r.walk() if s.name == "engine.decode")
    assert st["t_prefill_s"] == pytest.approx(admit * 1e-9)
    assert st["t_decode_s"] == pytest.approx(decode * 1e-9)
    assert st["t_prefill_s"] > 0 and st["decode_tok_s"] > 0
    syncs = sum(1 for r in roots for s in r.walk() if s.name == "engine.sync")
    assert st["syncs"] == syncs > 0


def test_request_spans_share_the_request_id(served):
    _, prompts, res, roots = served
    for r in res:
        mine = {s.name: s for s in roots
                if s.name.startswith("request.") and s.rid == r["rid"]}
        assert set(mine) == {"request.queue", "request.prefill",
                             "request.decode"}
        q, p, d = (mine["request.queue"], mine["request.prefill"],
                   mine["request.decode"])
        assert q.t1 == p.t0 and p.t1 == d.t0 and q.t0 <= d.t1
        assert d.t1 - q.t0 == pytest.approx(r["t_total_s"] * 1e9, abs=1e3)
    retired = [s.rid for r in roots for s in r.walk()
               if s.name == "engine.retire"]
    assert sorted(retired) == sorted(r["rid"] for r in res)


def test_serving_compiles_none_of_the_warmed_jits(served):
    _, _, _, roots = served
    funs = [s.attrs["fun"] for r in roots for s in r.walk()
            if s.name == "compile"]
    assert not [f for f in funs if any(j in f for j in ENGINE_JITS)], funs


def test_engine_with_the_recorder_off(served):
    eng, prompts, _, _ = served
    rec = tracing.RECORDER
    rec.clear()
    before = dict(eng.totals.ns)
    rec.enabled = False
    try:
        eng.submit(prompts[1].astype(np.int32), max_new=3)
        out = eng.run()
    finally:
        rec.enabled = True
    assert len(out[-1]["tokens"]) == 3
    assert not rec.roots and eng.totals.ns == before
