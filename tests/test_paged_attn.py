"""Paged-attention decode kernel + packed prefill acceptance.

Three contracts (see kernels/paged_attn.py and docs/serving.md):

  * the single-pass Pallas block-table kernel is **bit-identical** to
    the gather oracle (``paged_attention_reference``) across page sizes, ragged
    cache lengths, empty rows, int8 pools with scale planes, SWA page
    skipping and GQA group widths — so backend dispatch only ever trades
    bytes for bytes, never tokens.
  * the kernel's compiled HLO never materializes the gathered
    ``(B, NB*page, Hkv, dh)`` dequantized KV row — the whole point of
    walking the block table — while the gather oracle's HLO does
    (positive control for the shape probe).
  * packed prefill (several short prompts through one flash call with
    per-segment masking) retires token streams identical to unpacked
    chunked prefill, with strictly fewer prefill dispatches, and stays
    inside the AOT-warmed trace set.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.configs import get_config
from repro.kernels.autotune import TuningCache, paged_attn_key
from repro.kernels.ops import (paged_attention, paged_attention_reference,
                               tune_paged_attention)
from repro.kernels.paged_attn import (TRASH_PAGE, paged_attention_tpu,
                                      pages_read_per_step)
from repro.models import api
from repro.models.reduce import reduced
from repro.runtime.engine import Engine
from repro.runtime.serving import generate


# ---------------------------------------------------------------------------
# fixtures: pools with a pinned all-zero trash page
# ---------------------------------------------------------------------------

def _pools(rng, n_pages, page, hkv, dh, quant):
    """K/V pools with row TRASH_PAGE zeroed (the engine invariant: page 0
    is reserved and never written)."""
    if quant:
        kp = rng.randint(-127, 128, (n_pages, page, hkv, dh)).astype(np.int8)
        vp = rng.randint(-127, 128, (n_pages, page, hkv, dh)).astype(np.int8)
        ks = np.abs(rng.randn(n_pages, page, hkv)).astype(np.float32) * 0.05
        vs = np.abs(rng.randn(n_pages, page, hkv)).astype(np.float32) * 0.05
        kp[TRASH_PAGE] = 0
        vp[TRASH_PAGE] = 0
        ks[TRASH_PAGE] = 0
        vs[TRASH_PAGE] = 0
        return (jnp.asarray(kp), jnp.asarray(vp),
                jnp.asarray(ks, jnp.bfloat16), jnp.asarray(vs, jnp.bfloat16))
    kp = rng.randn(n_pages, page, hkv, dh).astype(np.float32)
    vp = rng.randn(n_pages, page, hkv, dh).astype(np.float32)
    kp[TRASH_PAGE] = 0
    vp[TRASH_PAGE] = 0
    return jnp.asarray(kp), jnp.asarray(vp), None, None


def _case(seed, B, page, nb, hkv, g, dh, quant):
    """One decode step: ragged cache_len per row (incl. a single-token
    row and an exact page boundary), live block entries distinct, dead
    entries deliberately garbage (they must never leak into the output).
    cache_len >= 1 throughout: decode never runs on an empty row, and a
    fully-masked softmax degenerates to a uniform average of whatever
    the backend staged — garbage either way."""
    rng = np.random.RandomState(seed)
    n_pages = 1 + B * nb
    kp, vp, ks, vs = _pools(rng, n_pages, page, hkv, dh, quant)
    q = jnp.asarray(rng.randn(B, 1, hkv * g, dh), jnp.float32)
    perm = 1 + rng.permutation(n_pages - 1)[:B * nb]
    block = jnp.asarray(perm.reshape(B, nb), jnp.int32)
    cl = np.minimum(
        np.array([1, page - 1, page, nb * page - 3][:B]), nb * page)
    if B > 4:
        cl = np.concatenate([cl, rng.randint(1, nb * page + 1, (B - 4,))])
    return q, kp, vp, block, jnp.asarray(cl, jnp.int32), ks, vs


# ---------------------------------------------------------------------------
# kernel == gather oracle, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("page,quant,window,g", [
    (8, False, None, 1),    # MHA-per-kv-head, dense
    (8, False, None, 2),    # GQA
    (8, True, None, 2),     # int8 pools + bf16 scale planes
    (8, False, 12, 2),      # SWA: behind-window pages skipped
    (8, True, 12, 1),       # SWA + int8
    (16, True, None, 2),    # engine page size
    (16, False, 24, 2),     # engine page size + SWA
])
def test_kernel_matches_gather_bitwise(page, quant, window, g):
    q, kp, vp, block, cl, ks, vs = _case(
        seed=page + 7 * g + (13 if quant else 0), B=4, page=page, nb=3,
        hkv=2, g=g, dh=16, quant=quant)
    got = paged_attention_tpu(q, kp, vp, block, cl, window=window,
                              k_scale=ks, v_scale=vs, interpret=True)
    want = paged_attention_reference(q, kp, vp, block, cl, window=window,
                                     k_scale=ks, v_scale=vs)
    assert got.dtype == want.dtype == q.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("quant,window", [
    (False, None), (True, None), (False, 12), (True, 12),
])
def test_multipass_vmem_split_bitwise(quant, window):
    """Forced-tiny VMEM budgets push the kernel onto the two-pass split
    (pass A: scores page by page, exact softmax over the whole row;
    pass B: V page by page into an f32 accumulator). The default budget
    stays single-pass and bit-identical to the gather oracle; the
    two-pass result sums V over pages, so it matches the oracle to f32
    rounding: a few ulps of the largest term (up to 7e-6 relative on
    the smallest outputs here)."""
    from repro.kernels.paged_attn import vmem_plan

    q, kp, vp, block, cl, ks, vs = _case(
        seed=11 + (3 if quant else 0), B=4, page=8, nb=3, hkv=2, g=2,
        dh=16, quant=quant)
    want = np.asarray(paged_attention_reference(
        q, kp, vp, block, cl, window=window, k_scale=ks, v_scale=vs))
    seen = set()
    for budget in (None, 2000, 300):
        plan = vmem_plan(3, 8, 16, 2, quant=quant, hkv=2,
                         kv_itemsize=kp.dtype.itemsize, budget_bytes=budget)
        seen.add(plan["multipass"])
        got = np.asarray(paged_attention_tpu(
            q, kp, vp, block, cl, window=window, k_scale=ks, v_scale=vs,
            interpret=True, vmem_budget_bytes=budget))
        msg = f"budget={budget} plan={plan}"
        if plan["multipass"]:
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5,
                                       err_msg=msg)
        else:
            np.testing.assert_array_equal(got, want, err_msg=msg)
    # the sweep must exercise both kernels
    assert seen == {False, True}


def test_vmem_plan_properties():
    """Planner invariants: the default budget keeps test-sized rows
    single-pass; a budget below the single-pass scratch switches to the
    two-pass kernel, whose scratch does not depend on the budget; both
    layouts count every KV head; the tiled (compiled) plan counts the
    (8, 128) padding, so danube's head_dim 80 costs 128 lanes."""
    from repro.kernels.paged_attn import DEFAULT_VMEM_BUDGET, vmem_plan

    p = vmem_plan(3, 8, 16, 2, quant=False, kv_itemsize=4)
    assert not p["multipass"]
    assert p["single_bytes"] == 2 * 3 * 8 * 16 * 4
    for budget in (3000, 1500, 800, 400, 200):
        b = vmem_plan(3, 8, 16, 2, quant=False, kv_itemsize=4,
                      budget_bytes=budget)
        assert b["multipass"] == (b["single_bytes"] > budget)
        assert b["multi_bytes"] == p["multi_bytes"]
    p4 = vmem_plan(3, 8, 16, 2, quant=False, kv_itemsize=4, hkv=4)
    assert p4["single_bytes"] == 4 * p["single_bytes"]
    assert p4["multi_bytes"] == 4 * p["multi_bytes"]
    # danube-1.8b int8 KV over its 4096-token window (65 pages of 64)
    d = vmem_plan(65, 64, 80, 4, quant=True, kv_itemsize=1, hkv=8,
                  tiled=True)
    assert d["single_bytes"] == 2 * 8 * 65 * 64 * 128 * 2
    assert d["multipass"] and d["multi_bytes"] < DEFAULT_VMEM_BUDGET


def test_dispatch_backends_agree():
    """ops.paged_attention routes both names to the same tokens-in,
    tokens-out function; "auto" with an empty cache takes the kernel."""
    q, kp, vp, block, cl, ks, vs = _case(
        seed=3, B=4, page=8, nb=2, hkv=1, g=2, dh=16, quant=False)
    outs = [paged_attention(q, kp, vp, block, cl, backend=b, interpret=True)
            for b in ("kernel", "gather", "auto")]
    np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(outs[1]))
    np.testing.assert_array_equal(np.asarray(outs[0]), np.asarray(outs[2]))
    with pytest.raises(ValueError):
        paged_attention(q, kp, vp, block, cl, backend="nope")


_ktpu = jax.jit(functools.partial(paged_attention_tpu, interpret=True))


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_dead_block_entries_never_leak(seed):
    """Property: block-table entries at or beyond cache_len are trash —
    pointing them at a poison page full of huge values changes nothing.
    (This is what lets the allocator recycle pages without scrubbing the
    tables of retired rows.)"""
    rng = np.random.RandomState(seed)
    page, nb, B = 8, 3, 2
    kp, vp, _, _ = _pools(rng, 1 + B * nb + 1, page, 1, 8, quant=False)
    poison = kp.shape[0] - 1
    kp = kp.at[poison].set(1e4)
    vp = vp.at[poison].set(-1e4)
    q = jnp.asarray(rng.randn(B, 1, 1, 8), jnp.float32)
    live = 1 + rng.permutation(B * nb).reshape(B, nb)
    cl = rng.randint(0, nb * page + 1, (B,))
    n_live = -(-cl // page)  # pages holding any pos < cache_len
    dead = np.arange(nb)[None, :] >= n_live[:, None]
    clean = np.where(dead, TRASH_PAGE, live).astype(np.int32)
    dirty = np.where(dead, poison, live).astype(np.int32)
    cl = jnp.asarray(cl, jnp.int32)
    a = _ktpu(q, kp, vp, jnp.asarray(clean), cl)
    b = _ktpu(q, kp, vp, jnp.asarray(dirty), cl)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# HLO: the kernel never materializes the gathered KV row
# ---------------------------------------------------------------------------

def test_hlo_never_materializes_kv_row():
    """The dequantized (B, NB*page, Hkv, dh) row is the bandwidth bill
    this kernel exists to avoid: the gather oracle's HLO carries it (as
    int8 gather + bf16 dequant), the kernel's HLO must not."""
    B, page, nb, hkv, dh = 2, 8, 4, 2, 16
    q, kp, vp, block, cl, ks, vs = _case(
        seed=11, B=B, page=page, nb=nb, hkv=hkv, g=2, dh=dh, quant=True)

    def lower(backend):
        def f(q, kp, vp, ks, vs, block, cl):
            return paged_attention(q, kp, vp, block, cl, k_scale=ks,
                                   v_scale=vs, backend=backend,
                                   interpret=True)

        return jax.jit(f).lower(q, kp, vp, ks, vs, block, cl).as_text()

    row = f"{B}x{nb * page}x{hkv}x{dh}"
    gather_txt = lower("gather")
    kernel_txt = lower("kernel")
    # positive control: the probe string is the right spelling
    assert f"tensor<{row}xbf16>" in gather_txt
    assert f"tensor<{row}xbf16>" not in kernel_txt
    assert f"tensor<{row}xi8>" not in kernel_txt


# ---------------------------------------------------------------------------
# bytes model the benches/CI gate on
# ---------------------------------------------------------------------------

def test_pages_read_model():
    # dense: live span ceil(cl/page), +1 trash page when any step is dead
    assert pages_read_per_step(0, 16, 4) == 1
    assert pages_read_per_step(1, 16, 4) == 2
    assert pages_read_per_step(40, 16, 4) == 4
    assert pages_read_per_step(64, 16, 4) == 4
    # SWA: only pages intersecting (cl-window, cl] are live
    assert pages_read_per_step(64, 16, 4, window=16) == 2
    assert pages_read_per_step(60, 16, 4, window=16) == 3
    # the model never exceeds the gather oracle's nb pages (+trash)
    for cl in range(0, 65, 7):
        assert pages_read_per_step(cl, 16, 4) <= 4 + 1
        assert (pages_read_per_step(cl, 16, 4, window=16)
                <= pages_read_per_step(cl, 16, 4))


def test_tune_paged_attention_records_winner():
    tc = TuningCache()
    key, tile, timings = tune_paged_attention(
        batch=2, page=8, pages_per_row=2, hkv=1, dh=8, g=1,
        interpret=True, reps=1, warmup=0, cache=tc)
    assert key == paged_attn_key(8, 2, 1, 8, jnp.float32, interpret=True)
    assert {k.rsplit("/", 1)[1] for k in timings} == {"kernel", "gather"}
    assert tc.get(key) == tile and tile.strategy in ("kernel", "gather")


# ---------------------------------------------------------------------------
# packed prefill: same tokens, fewer dispatches
# ---------------------------------------------------------------------------

def _fp_setup(arch):
    cfg = reduced(get_config(arch)).replace(quant=None, act_bits=32,
                                            remat=False)
    params, _ = api.init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _solo(params, cfg, toks, steps, max_len):
    return np.asarray(
        generate(params, cfg, {"tokens": jnp.asarray(toks[None])},
                 steps=steps, max_len=max_len))[0]


PACK_LENS = [5, 7, 6, 8]


def _run_pack(params, cfg, toks, pack, **ekw):
    eng = Engine(params, cfg, capacity=4, max_len=20, kv_pages=24,
                 page_size=16, prefill_pack=pack, **ekw)
    assert eng.paged and eng.prefill_pack == (pack and cfg.act_bits >= 32)
    traces = eng.paged_trace_counts()
    for i, L in enumerate(PACK_LENS):
        eng.submit(toks[i, :L], max_new=4)
    res = eng.run()
    assert eng.paged_trace_counts() == traces, "packing added jit traces"
    eng.pkv.alloc.check()
    return res, eng.stats()


@pytest.mark.slow
def test_packed_prefill_token_parity_and_fewer_calls():
    """Packing co-admitted prompts into one flash call is invisible in
    the tokens (segment masking + kv-block-aligned bases) and visible in
    the dispatch count: one packed call replaces N chunk calls."""
    cfg, params = _fp_setup("mistral-nemo-12b")
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (4, 8),
                                         0, cfg.vocab), np.int32)
    packed, sp = _run_pack(params, cfg, toks, pack=True)
    plain, su = _run_pack(params, cfg, toks, pack=False)
    assert sp["packed_groups"] >= 1 and sp["packed_requests"] >= 2
    assert su["packed_groups"] == 0
    assert (sp["prefill_chunk_calls"] + sp["packed_groups"]
            < su["prefill_chunk_calls"])
    for i, L in enumerate(PACK_LENS):
        np.testing.assert_array_equal(
            packed[i]["tokens"], plain[i]["tokens"],
            err_msg=f"packed request {i} diverged")
        want = _solo(params, cfg, toks[i, :L], 4, 20)
        np.testing.assert_array_equal(packed[i]["tokens"], want,
                                      err_msg=f"solo parity, request {i}")


@pytest.mark.slow
def test_packed_prefill_parity_int8_kv():
    """int8 KV: packed segments quantize at the splice with per-token
    scales, identical to the chunked path. Prefix cache off — hit
    patterns depend on admission order and int8 hydrate is lossy, so
    sharing would compare different roundings, not packing itself."""
    cfg, params = _fp_setup("mistral-nemo-12b")
    cfg = cfg.replace(kv_cache_bits=8)
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (4, 8),
                                         0, cfg.vocab), np.int32)
    packed, sp = _run_pack(params, cfg, toks, pack=True, prefix_cache=False)
    plain, _ = _run_pack(params, cfg, toks, pack=False, prefix_cache=False)
    assert sp["packed_groups"] >= 1
    for i in range(len(PACK_LENS)):
        np.testing.assert_array_equal(
            packed[i]["tokens"], plain[i]["tokens"],
            err_msg=f"int8 packed request {i} diverged")


def test_packing_disabled_under_dynamic_act_quant():
    """Dynamic activation fake-quant scales are per-tensor maxima —
    batch-global state that couples co-packed rows — so the engine must
    refuse to pack when act_bits < 32."""
    cfg, params = _fp_setup("mistral-nemo-12b")
    cfg = cfg.replace(act_bits=8)
    eng = Engine(params, cfg, capacity=4, max_len=20, kv_pages=24,
                 page_size=16, prefill_pack=True)
    assert eng.paged and not eng.prefill_pack
