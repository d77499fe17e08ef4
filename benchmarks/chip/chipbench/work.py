"""Operations and bytes that each piece of work needs, from its shapes.

Counted the same whatever implements the work: a kernel that pads, or
decodes more than it needs, does not earn more work by it. The counting
of parameters and FLOPs follows ``benchmarks/roofline.py``
(``param_groups``, ``forward_flops``), restricted to the dense decoder
that the cells run.

``m`` is the dict of model sizes that ``harness.model_dims`` reads from
a configuration file.
"""
from __future__ import annotations

PACKED_BYTES_PER_WEIGHT = 0.5   # 4-bit index plane
DICT_BYTES = 16 * 4             # 16 float32 dictionary entries
ACT_BYTES = 2                   # bfloat16 activations in
OUT_BYTES = 4                   # float32 kernel outputs


def layer_matmuls(m: dict) -> list:
    """(Kin, N) of the quantized projections of one layer, in call order."""
    d, hd = m["d_model"], m["head_dim"]
    h, kv, ff = m["n_heads"], m["n_kv_heads"], m["d_ff"]
    return [(d, h * hd), (d, kv * hd), (d, kv * hd), (h * hd, d),
            (d, ff), (d, ff), (ff, d)]


def matmul_params(m: dict) -> int:
    """Weights one token multiplies: the layer projections and the head."""
    body = sum(k * n for k, n in layer_matmuls(m)) * m["n_layers"]
    return body + m["d_model"] * m["vocab"]


def attn_flops(m: dict, ctx: int) -> float:
    """QK^T and PV of one token against ``ctx`` keys, all layers."""
    return 4.0 * m["n_heads"] * m["head_dim"] * ctx * m["n_layers"]


def attended(m: dict, pos: int) -> int:
    """Keys a query at position ``pos`` attends (sliding window aware)."""
    w = m.get("window")
    return min(pos + 1, w) if w else pos + 1


def lutq_dot_call(mrows: int, kin: int, n: int):
    """(flops, bytes) of one packed LUT-Q matmul: (M, Kin) @ d[a]."""
    flops = 2.0 * mrows * kin * n
    bytes_ = (kin * n * PACKED_BYTES_PER_WEIGHT + DICT_BYTES
              + mrows * kin * ACT_BYTES + mrows * n * OUT_BYTES)
    return flops, bytes_


def pages_read(ctx: int, page: int, window=None) -> int:
    """KV pages that one row's attention must read: those holding the
    keys it attends, the last ``window`` of its ``ctx`` keys where the
    model has a window. A dead row (``ctx`` 0) reads none."""
    if ctx <= 0:
        return 0
    first = 0 if window is None else max(0, (ctx - window) // page)
    return (ctx - 1) // page - first + 1


def paged_attn_step(m: dict, ctxs, page: int):
    """(flops, bytes) of the paged decode attention of one step over all
    layers: KV pages read at each live row's length, plus q and out.
    ``ctxs`` holds the keys each live row attends."""
    hkv, hd, h = m["n_kv_heads"], m["head_dim"], m["n_heads"]
    kv_page = page * hkv * hd * 2 * ACT_BYTES
    f = b = 0.0
    for c in ctxs:
        f += 4.0 * h * hd * min(c, m.get("window") or c)
        b += pages_read(c, page, m.get("window")) * kv_page
        b += 2 * h * hd * ACT_BYTES
    return f * m["n_layers"], b * m["n_layers"]


def model_flops_step(m: dict, decode_ctxs, prefill):
    """Model FLOPs of one engine step: 2 N_matmul per computed token plus
    attention at its context. ``decode_ctxs``: keys attended by each live
    decode row; ``prefill``: (start, n_real) of each prompt segment the
    step's prefill computed, the real tokens at positions start ..
    start + n_real - 1, with the head run once a segment."""
    n = matmul_params(m)
    head = m["d_model"] * m["vocab"]
    f = 0.0
    for c in decode_ctxs:
        f += 2.0 * n + attn_flops(m, attended(m, c - 1))
    for start, n_real in prefill:
        f += 2.0 * (n - head) * n_real + 2.0 * head
        f += sum(attn_flops(m, attended(m, p))
                 for p in range(start, start + n_real))
    return f


def kernel_match(kernel: str, m: dict = None, rows: int = 0):
    """Predicate on a TPU trace op name for one kernel's calls. The packed
    LUT-Q matmul keeps its kernel function's name; the paged attention
    kernel shows as an anonymous custom call, known by its result, the
    (rows, kv heads, group, head_dim) bf16 attention output."""
    if kernel == "lutq_dot":
        return lambda n: ("custom-call(" in n and
                          n.lstrip("%").startswith(("lutq_gemv_packed",
                                                    "lutq_matmul")))
    if kernel == "paged_attn":
        g = m["n_heads"] // m["n_kv_heads"]
        out = f"bf16[{rows},{m['n_kv_heads']},{g},{m['head_dim']}]"
        return lambda n: ("custom-call(" in n and " = " in n
                          and n.split(" = ", 1)[1].startswith(out))
    raise ValueError(kernel)


def min_time(flops: float, bytes_: float, peaks: dict) -> float:
    """Least seconds the chip needs for one call: the larger bound."""
    return max(flops / peaks["bf16_flops"], bytes_ / peaks["hbm_bytes_per_s"])


def lutq_dot_min_time(m: dict, rows: int, head_rows: int,
                      peaks: dict) -> float:
    """Sum over one model step's lutq_dot calls of their least time."""
    t = sum(min_time(*lutq_dot_call(rows, kin, n), peaks)
            for kin, n in layer_matmuls(m)) * m["n_layers"]
    if not m["tie_embeddings"] and head_rows:
        t += min_time(*lutq_dot_call(head_rows, m["d_model"], m["vocab"]),
                      peaks)
    return t
