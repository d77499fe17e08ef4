"""Profiler trace -> events -> device busy time, kernel time, idle gaps.

``capture`` writes an XPlane trace with ``jax.profiler`` and converts it
to a plain dict (``{"planes": [{"name", "lines": [{"name", "events":
[[name, start_ns, dur_ns], ...]}]}]}``), which is what every reduction
below reads, and what the recorded trace under ``tests/data`` holds.
Timestamps of all planes share the profiler's clock.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil
import tempfile

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


@contextlib.contextmanager
def capture(out: dict):
    """Trace the body; on exit ``out["planes"]`` holds the events."""
    import jax

    tmp = tempfile.mkdtemp(prefix="chipbench_trace_")
    try:
        jax.profiler.start_trace(tmp)
        try:
            yield out
        finally:
            jax.profiler.stop_trace()
        out.update(load(tmp))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def load(logdir: str) -> dict:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise RuntimeError(f"no .xplane.pb under {logdir}")
    pd = ProfileData.from_file(paths[-1])
    planes = []
    for pl in pd.planes:
        lines = []
        for ln in pl.lines:
            ev = [[e.name, float(e.start_ns), float(e.duration_ns)]
                  for e in ln.events]
            if ev:
                lines.append({"name": ln.name, "events": ev})
        if lines:
            planes.append({"name": pl.name, "lines": lines})
    return {"planes": planes}


def device_ops(tr: dict):
    """{device plane name: [(name, start_ns, dur_ns), ...]} from the op
    line of every TPU plane."""
    out = {}
    for pl in tr["planes"]:
        if not pl["name"].startswith(DEVICE_PREFIX):
            continue
        for ln in pl["lines"]:
            if ln["name"] == OPS_LINE:
                out[pl["name"]] = [tuple(e) for e in ln["events"]]
    return out


def host_spans(tr: dict, names) -> list:
    """(name, start_ns, dur_ns) of the host events named in ``names``."""
    out = []
    for pl in tr["planes"]:
        if pl["name"] != HOST_PLANE:
            continue
        for ln in pl["lines"]:
            out += [tuple(e) for e in ln["events"] if e[0] in names]
    return sorted(out, key=lambda e: e[1])


def union(intervals) -> list:
    """Merged [start, end) intervals of (start, end) pairs."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def busy_ns(ops, lo: float, hi: float) -> float:
    """Length of the union of op intervals clipped to [lo, hi)."""
    iv = [(max(s, lo), min(s + d, hi)) for _, s, d in ops
          if s < hi and s + d > lo]
    return sum(e - s for s, e in union(iv))


def gaps(ops, lo: float, hi: float) -> list:
    """Idle (start, end) intervals of the device within [lo, hi)."""
    out, t = [], lo
    for s, e in union([(max(s, lo), min(s + d, hi)) for _, s, d in ops
                       if s < hi and s + d > lo]):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def op_seconds(ops, match) -> float:
    """Summed device seconds of the ops whose name satisfies ``match``."""
    return sum(d for n, _, d in ops if match(n)) * 1e-9


def is_container(name: str) -> bool:
    """A loop or branch op, whose interval holds the ops inside it."""
    return re.match(r"^%?(while|conditional)\.", name) is not None


def label(name: str) -> str:
    """Short form of an XLA op's trace name: its HLO name, result type
    and opcode ("lutq_gemv_packed.71 f32[32,2560] custom-call")."""
    if " = " not in name:
        return name[:120]
    lhs, rhs = name.split(" = ", 1)
    typ = rhs.split("{", 1)[0].split(" ", 1)[0]
    rest = rhs.split("} ", 1)[1] if "} " in rhs else rhs
    return f"{lhs.lstrip('%')} {typ} {rest.split('(', 1)[0]}"[:120]


def top_ops(ops, n: int = 10) -> list:
    """The ``n`` op labels with the most device time, loops left out."""
    tot = {}
    for name, _, d in ops:
        if is_container(name):
            continue
        key = label(name)
        tot[key] = tot.get(key, 0.0) + d
    return [[k, v * 1e-9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def attributed_gaps(gap_list, spans, n: int = 10) -> list:
    """The ``n`` longest gaps, each named by the innermost host span open
    at its midpoint ("none" where the host was in none)."""
    out = []
    for s, e in sorted(gap_list, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) / 2
        inner = [sp for sp in spans if sp[1] <= mid < sp[1] + sp[2]]
        name = min(inner, key=lambda sp: sp[2])[0] if inner else "none"
        out.append([name, (e - s) * 1e-9])
    return out


def reduce(tr: dict, lo: float, hi: float, span_names) -> dict:
    """Busy and window seconds (averaged over the device planes), the
    ops inside [lo, hi) of the first device, and the breakdown."""
    per_dev = device_ops(tr)
    if not per_dev:
        raise RuntimeError("the trace holds no TPU op events")
    window = (hi - lo) * 1e-9
    busy = sum(busy_ns(o, lo, hi) for o in per_dev.values()) / len(per_dev)
    first = per_dev[sorted(per_dev)[0]]
    ops = [o for o in first if lo <= o[1] < hi]
    spans = host_spans(tr, span_names)
    return {
        "busy_s": busy * 1e-9, "window_s": window, "ops": ops,
        "breakdown": {"device_ops": top_ops(ops),
                      "idle_gaps": attributed_gaps(gaps(first, lo, hi),
                                                   spans)},
    }
