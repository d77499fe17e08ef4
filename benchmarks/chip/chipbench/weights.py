"""Seeded LUT-Q weights, made by the benchmark, and their served form.

The benchmark owns the weights: every quantized tensor is a sorted
16-entry power-of-two dictionary ``d`` and an int8 index plane ``a``
drawn from ``--seed``, every norm gain a float vector. The plain
reference (``reference.py``) rebuilds the same tensors from the seed with
these functions and decodes ``d[a]`` itself; it never sees what the
program made from them.

``served_tree`` hands the same tensors to the system under test through
its own ``serve_view`` (pack4), one layer at a time into preallocated
stacks, so a model whose f32 copy would not fit on the chip (Nemo: 49 GB)
never holds more than one layer's draws.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

K_DICT = 16  # 4-bit dictionary
LAYER_TENSORS = ("q", "k", "v", "o", "wi", "wg", "wo")


def root_key(seed: int) -> jax.Array:
    """A PRNG key for any whole-number seed, 64-bit ones included."""
    seed = int(seed)
    lo, hi = seed & 0x7FFFFFFF, (seed >> 31) & 0x7FFFFFFF
    return jax.random.fold_in(jax.random.PRNGKey(lo), hi)


def shapes(m: dict) -> dict:
    """(Kin, N) of every quantized tensor of one layer, and the std of the
    weights its dictionary is fitted to. Output projections are scaled
    down by sqrt(2 * layers), as in GPT-2's initialisation, so the
    residual stream stays near unit scale through the depth."""
    d, hd = m["d_model"], m["head_dim"]
    h, kv, ff = m["n_heads"], m["n_kv_heads"], m["d_ff"]
    damp = math.sqrt(2.0 * m["n_layers"])
    return {
        "q": ((d, h * hd), d ** -0.5),
        "k": ((d, kv * hd), d ** -0.5),
        "v": ((d, kv * hd), d ** -0.5),
        "o": ((h * hd, d), (h * hd) ** -0.5 / damp),
        "wi": ((d, ff), d ** -0.5),
        "wg": ((d, ff), d ** -0.5),
        "wo": ((ff, d), ff ** -0.5 / damp),
    }


def dictionary(key, std):
    """A sorted 16-entry pow2 dictionary whose top magnitude sits within
    [1.25, 2.5) std (one seeded octave of spread)."""
    e_top = (jnp.floor(jnp.log2(2.5 * std))
             - jax.random.randint(key, (), 0, 2)).astype(jnp.float32)
    mags = 2.0 ** (e_top - jnp.arange(K_DICT // 2, dtype=jnp.float32))
    return jnp.concatenate([-mags, mags[::-1]])


def nearest(w, d):
    """int8 index of the dictionary entry nearest each weight: one
    compare per midpoint, elementwise, so it fuses into one pass."""
    mid = (d[1:] + d[:-1]) * 0.5
    a = jnp.zeros(w.shape, jnp.int8)
    for k in range(K_DICT - 1):
        a = a + (w > mid[k]).astype(jnp.int8)
    return a


def lutq_tensor(key, shape, std):
    """(d (16,) f32, a int8 ``shape``): a dictionary and the nearest-entry
    indices of normal(0, std) draws."""
    kd, kw = jax.random.split(key)
    d = dictionary(kd, std)
    return d, nearest(jax.random.normal(kw, shape, jnp.float32) * std, d)


VOCAB_BLOCK = 8192


def vocab_blocks(vocab: int) -> int:
    """How many equal blocks of at most ``VOCAB_BLOCK`` rows the
    vocabulary is drawn in (the fewest that divide it)."""
    n = -(-vocab // VOCAB_BLOCK)
    while vocab % n:
        n += 1
    return n


def _vocab_keys(seed_key, which: int):
    return jax.random.split(jax.random.fold_in(seed_key, which))


def embed_dict(seed_key, m):
    # the program scales embeddings by sqrt(d_model): unit rows after it
    return dictionary(_vocab_keys(seed_key, 2)[0], m["d_model"] ** -0.5)


def embed_block(seed_key, m, b):
    """Rows [b * vb, (b + 1) * vb) of the embedding's index plane."""
    vb = m["vocab"] // vocab_blocks(m["vocab"])
    std = m["d_model"] ** -0.5
    w = jax.random.normal(jax.random.fold_in(_vocab_keys(seed_key, 2)[1], b),
                          (vb, m["d_model"]), jnp.float32) * std
    return nearest(w, embed_dict(seed_key, m))


def head_dict(seed_key, m):
    return dictionary(_vocab_keys(seed_key, 3)[0], m["d_model"] ** -0.5)


def head_block(seed_key, m, b):
    """Columns [b * vb, (b + 1) * vb) of the head's index plane."""
    vb = m["vocab"] // vocab_blocks(m["vocab"])
    std = m["d_model"] ** -0.5
    w = jax.random.normal(jax.random.fold_in(_vocab_keys(seed_key, 3)[1], b),
                          (m["d_model"], vb), jnp.float32) * std
    return nearest(w, head_dict(seed_key, m))


def embed_raw(seed_key, m):
    n = vocab_blocks(m["vocab"])
    return embed_dict(seed_key, m), jnp.concatenate(
        [embed_block(seed_key, m, b) for b in range(n)], 0)


def head_raw(seed_key, m):
    n = vocab_blocks(m["vocab"])
    return head_dict(seed_key, m), jnp.concatenate(
        [head_block(seed_key, m, b) for b in range(n)], 1)


def norm_gain(key, dim):
    return jax.random.uniform(key, (dim,), jnp.float32, 0.8, 1.2)


def layer_raw(key, m: dict) -> dict:
    """One layer in the benchmark's own form: {name: (d, a)} and gains."""
    out = {}
    for i, (name, (shape, std)) in enumerate(shapes(m).items()):
        out[name] = lutq_tensor(jax.random.fold_in(key, i), shape, std)
    out["ln1"] = norm_gain(jax.random.fold_in(key, 100), m["d_model"])
    out["ln2"] = norm_gain(jax.random.fold_in(key, 101), m["d_model"])
    return out


def layer_key(seed_key, i):
    return jax.random.fold_in(jax.random.fold_in(seed_key, 1), i)


def final_norm_raw(seed_key, m):
    return norm_gain(jax.random.fold_in(seed_key, 4), m["d_model"])


# ---------------------------------------------------------------------------
# the program's served form
# ---------------------------------------------------------------------------

def _program_layer(raw: dict, state_cls) -> dict:
    def q(name):
        d, a = raw[name]
        return {"kernel": state_cls(w=None, d=d, a=a)}

    return {
        "ln1": {"scale": raw["ln1"]}, "ln2": {"scale": raw["ln2"]},
        "attn": {n: q(n) for n in ("q", "k", "v", "o")},
        "mlp": {n: q(n) for n in ("wi", "wg", "wo")},
    }


def served_tree(cfg, m: dict, seed: int):
    """The served (pack4) tree of the program for the seed's weights.

    Built on the device: one compiled program makes a layer and writes it
    into donated stacks, so the peak holds the served stacks plus one
    layer's draws."""
    from repro.core.lutq import LutqState
    from repro.core.policy import serve_view
    from repro.models import api

    policy = api.resolved_policy(cfg)
    skey = root_key(seed)

    def view(tree):
        return serve_view(tree, pack4=True, policy=policy)

    def one_layer(skey, i):
        raw = layer_raw(layer_key(skey, i), m)
        return view({"layers": _program_layer(raw, LutqState)})["layers"]

    # the key is an argument, never a constant of the programs, so every
    # seed runs the same compiled programs
    n = m["n_layers"]
    shape1 = jax.eval_shape(one_layer, skey, 0)
    stacks = jax.jit(lambda: jax.tree.map(
        lambda s: jnp.zeros((n,) + s.shape, s.dtype), shape1))()

    @functools.partial(jax.jit, donate_argnums=0)
    def put(stacks, skey, i):
        layer = one_layer(skey, i)
        return jax.tree.map(
            lambda s, x: jax.lax.dynamic_update_index_in_dim(s, x, i, 0),
            stacks, layer)

    for i in range(n):
        stacks = put(stacks, skey, jnp.int32(i))

    def rest(skey):
        d, a = embed_raw(skey, m)
        tree = {"embed": {"table": LutqState(w=None, d=d, a=a)},
                "final_norm": {"scale": final_norm_raw(skey, m)}}
        if not m["tie_embeddings"]:
            hd, ha = head_raw(skey, m)
            tree["lm_head"] = {"kernel": LutqState(w=None, d=hd, a=ha)}
        return view(tree)

    params = jax.jit(rest)(skey)
    params["layers"] = stacks
    return jax.block_until_ready(params)


def served_bytes(tree) -> int:
    return int(sum(np.prod(x.shape) * x.dtype.itemsize
                   for x in jax.tree.leaves(tree)))
