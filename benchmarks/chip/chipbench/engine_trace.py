"""Per-layer quantities read from the program's own step records.

The engine records each ``Engine.step`` as an ``engine.step`` span, with
its child spans, its counters and its prefill notes, in the recorder of
``repro.runtime.tracing``, on the host clock that the benchmark's steps
keep. A reader takes the records whose ``engine.step`` begins inside
the traced steps, from the first one's start to the last one's end, and
returns None where the program keeps no such records.
"""
from __future__ import annotations


def step_records(ctx):
    """The engine's step records of the traced steps, or None."""
    try:
        from repro.runtime import tracing
    except ImportError:
        return None
    steps = ctx["steps"]
    if not steps:
        return None
    recs = tracing.RECORDER.records("engine.step", steps[0]["t0"] * 1e9,
                                    steps[-1]["t1"] * 1e9)
    return recs or None


def engine_host_ms(ctx):
    """Mean host time of a traced engine step outside its device syncs,
    in ms: ``engine.step`` less its ``engine.sync`` spans."""
    recs = step_records(ctx)
    if not recs:
        return None
    host = [r.ns - sum(s.ns for s in r.walk() if s.name == "engine.sync")
            for r in recs]
    return 1e-6 * sum(host) / len(host)


def prefill_pad_share(ctx):
    """Padding among the prefill tokens the traced steps computed, in %:
    sum of width less real tokens over sum of width."""
    recs = step_records(ctx)
    if not recs:
        return None
    entries = [e for r in recs for e in r.notes.get("prefill", ())]
    width = sum(w for _, _, w in entries)
    if not width:
        return None
    return 100.0 * (width - sum(n for _, n, _ in entries)) / width


def compiles_in_steps(ctx):
    """Compiles inside the traced steps."""
    recs = step_records(ctx)
    if recs is None:
        return None
    return float(sum(1 for r in recs for s in r.walk()
                     if s.name == "compile"))
