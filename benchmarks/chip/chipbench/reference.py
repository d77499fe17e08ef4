"""Plain float32 reference of the served LUT-Q language model.

Written from the configuration alone: a Mistral-style decoder (RMSNorm,
grouped-query attention with rotary positions and an optional sliding
window, SiLU-gated MLP, untied head) with every projection weight the
decoded LUT-Q tensor ``d[a]`` and 8-bit symmetric fake-quant of the
activations that enter the quantized projections (the LUT-Q paper's
serving regime; ``act_bits`` in the configuration). It imports nothing
of the system under test and rebuilds the weights from the seed
(``weights.py``), one layer at a time, so it fits beside nothing.

Departures from the published architectures, followed because the
configuration as run has them: token embeddings are scaled by
sqrt(d_model) before the first layer, and RMSNorm uses eps 1e-6. The
activation scale is taken per token (max |x| of the row): the served
program takes one scale per tensor, which couples the rows of a batch,
so no per-request replay can reproduce it; per-token is the finest
8-bit grid and independent of batching.

``lowp`` makes the control: the same computation with every matmul
operand and the residual stream rounded to float8 (e4m3, per-row
scaled), the next precision below the configuration's bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W

EPS = 1e-6
PAD_TO = 512
ROW_PAD = 128
FP8_MAX = 448.0


def _fq8(x):
    """Per-row symmetric 8-bit fake-quant (127 levels each side)."""
    s = jnp.maximum(jnp.max(jnp.abs(x), -1, keepdims=True), 1e-8) / 127.0
    return jnp.clip(jnp.round(x / s), -128, 127) * s


def _fp8(x):
    """Round to float8 e4m3 with a per-row scale (the control)."""
    s = jnp.maximum(jnp.max(jnp.abs(x), -1, keepdims=True), 1e-30) / FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _rms(x, g):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * g


def _rope(x, pos, theta):
    """Rotary embedding, half-split (GPT-NeoX) convention. x: (S, H, dh)."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _decode(t):
    d, a = t
    return d[a.astype(jnp.int32)]


def _attention(q, k, v, window, q_block=512):
    """Causal (optionally windowed) GQA attention, in query blocks so an
    8k sequence never holds all its scores. q: (S, H, dh); k, v:
    (S, Hkv, dh)."""
    S, H, dh = q.shape
    hkv = k.shape[1]
    g = H // hkv
    pad = (-S) % q_block
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, q_block, hkv, g, dh) * dh ** -0.5
    kpos = jnp.arange(S)

    def block(args):
        qb, i = args
        qpos = i * q_block + jnp.arange(q_block)
        s = jnp.einsum("qhgd,khd->hgqk", qb, k)
        ok = qpos[:, None] >= kpos[None, :]
        if window is not None:
            ok &= qpos[:, None] - kpos[None, :] < window
        s = jnp.where(ok, s, -jnp.inf)
        p = jax.nn.softmax(s, -1)
        return jnp.einsum("hgqk,khd->qhgd", p, v)

    o = jax.lax.map(block, (qp, jnp.arange(qp.shape[0])))
    return o.reshape(-1, H, dh)[:S]


@functools.partial(jax.jit, static_argnames=("m", "lowp"))
def _layer_weights(key, m, lowp):
    """One layer's decoded f32 weights (float8-rounded for the control)."""
    raw = W.layer_raw(key, dict(m))
    rnd = _fp8 if lowp else (lambda x: x)
    out = {n: rnd(_decode(raw[n])) for n in W.LAYER_TENSORS}
    out["ln1"], out["ln2"] = raw["ln1"], raw["ln2"]
    return out


@functools.partial(jax.jit, static_argnames=("m", "lowp"))
def _layer(h, wt, m, lowp):
    """One decoder layer over one sequence h: (S, D) f32."""
    md = dict(m)
    rnd = _fp8 if lowp else (lambda x: x)
    mm = (lambda x, name: rnd(x) @ wt[name])
    S = h.shape[0]
    hd, H, hkv = md["head_dim"], md["n_heads"], md["n_kv_heads"]
    pos = jnp.arange(S)
    x = _fq8(_rms(h, wt["ln1"]))
    q = _rope(mm(x, "q").reshape(S, H, hd), pos, md["rope_theta"])
    k = _rope(mm(x, "k").reshape(S, hkv, hd), pos, md["rope_theta"])
    v = mm(x, "v").reshape(S, hkv, hd)
    o = _attention(rnd(q), rnd(k), rnd(v), md["window"])
    h = rnd(h + mm(_fq8(o.reshape(S, H * hd)), "o"))
    x = _fq8(_rms(h, wt["ln2"]))
    u = mm(x, "wi") * jax.nn.silu(mm(x, "wg"))
    return rnd(h + mm(_fq8(u), "wo"))


@functools.partial(jax.jit, static_argnames=("m",))
def _embed_part(tokens, skey, m, b):
    """Embedding rows of the tokens that fall in vocabulary block ``b``
    (zeros elsewhere), scaled by sqrt(d_model) as the program does."""
    md = dict(m)
    vb = md["vocab"] // W.vocab_blocks(md["vocab"])
    d = W.embed_dict(skey, md)
    rows = d[W.embed_block(skey, md, b).astype(jnp.int32)]
    ids = tokens - b * vb
    inside = (ids >= 0) & (ids < vb)
    e = rows[jnp.clip(ids, 0, vb - 1)] * inside[:, None]
    return e * jnp.sqrt(jnp.float32(md["d_model"]))


@functools.partial(jax.jit, static_argnames=("m", "lowp"))
def _head_part(x, skey, m, b, lowp):
    """Logits of rows x over vocabulary block ``b``."""
    md = dict(m)
    rnd = _fp8 if lowp else (lambda x: x)
    w = W.head_dict(skey, md)[W.head_block(skey, md, b).astype(jnp.int32)]
    return rnd(x) @ rnd(w)


@functools.partial(jax.jit, static_argnames=("m",))
def _final_norm(h, skey, m):
    return _rms(h, W.final_norm_raw(skey, dict(m)))


def logits(m: dict, seed: int, seqs, rows, *, lowp: bool = False):
    """Reference logits at rows [start, start + count) of each token
    sequence, for (start, count) in ``rows``.

    Layer by layer over all sequences, so one layer's f32 weights are on
    the device at a time; the embedding and the head are decoded one
    vocabulary block at a time. Returns a list of (count, vocab) f32
    numpy arrays."""
    mt = tuple(sorted(m.items()))
    skey = W.root_key(seed)
    nvb = W.vocab_blocks(m["vocab"])
    # pad to whole blocks so few shapes compile: causal attention and
    # per-row scales keep the real rows independent of the padding
    padded = [np.pad(np.asarray(s, np.int32), (0, (-len(s)) % PAD_TO))
              for s in seqs]
    with jax.default_matmul_precision("highest"):
        hs = []
        for s in padded:
            t = jnp.asarray(s)
            e = _embed_part(t, skey, mt, 0)
            for b in range(1, nvb):
                e = e + _embed_part(t, skey, mt, b)
            hs.append(e)
        for i in range(m["n_layers"]):
            wt = _layer_weights(W.layer_key(skey, i), mt, lowp)
            hs = [_layer(h, wt, mt, lowp) for h in hs]
            del wt
        x = jnp.concatenate([h[a:a + n] for h, (a, n) in zip(hs, rows)])
        del hs
        total = x.shape[0]
        x = _final_norm(jnp.pad(x, ((0, (-total) % ROW_PAD), (0, 0))),
                        skey, mt)
        lg = np.concatenate([np.asarray(_head_part(x, skey, mt, b, lowp))
                             for b in range(nvb)], 1)[:total]
    out, at = [], 0
    for _, n in rows:
        out.append(lg[at:at + n])
        at += n
    return out


def served_gaps(m: dict, seed: int, samples, *, lowp: bool = False):
    """For each (prompt, served tokens) pair: the gap by which each served
    token's reference logit lies below the reference's best at its
    position. With ``lowp`` the tokens are the control's own greedy
    choices at the same positions, judged against the float32 logits."""
    seqs = [np.concatenate([p, s[:-1]]).astype(np.int32) for p, s in samples]
    rows = [(len(p) - 1, len(s)) for p, s in samples]
    ref = logits(m, seed, seqs, rows)
    low = logits(m, seed, seqs, rows, lowp=True) if lowp else None
    out = []
    for j, ((p, s), at) in enumerate(zip(samples, ref)):
        toks = (np.argmax(low[j], -1) if lowp
                else np.asarray(s, np.int64))
        gap = at.max(-1) - np.take_along_axis(at, toks[:, None], 1)[:, 0]
        out.append(gap.astype(np.float64))
    return out
