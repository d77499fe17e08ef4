"""Host spans and counters of the serving engine, in one flight recorder.

``RECORDER`` is process-wide and used the way ``logging`` is: the
engine writes to it, an operator or a benchmark reads it, and a test may
``clear()`` it. It keeps three kinds of record.

* Spans. ``with span("engine.admit", rid=3):`` records ``(name, t0,
  t1, parent, rid, attrs)`` on ``time.perf_counter_ns``, the clock of
  ``Request.t_*``. Each span also enters a
  ``jax.profiler.TraceAnnotation`` of the same name, so a profiler
  session shows it on the host plane, on the clock of the device ops.
  ``add`` records a span whose ends were timed elsewhere.
* Counters. ``count(name, n)`` adds to cumulative totals and to the
  record of the open root span (the engine's ``engine.step``);
  ``note(name, entry)`` appends an entry to that record.
* Compiles. Every backend compile that jax reports while a span is open
  becomes a ``compile`` span under the innermost open span, and counts
  as ``compiles``.

Finished root spans, each with its tree of children, go into a ring of
the last ``capacity`` roots. A root may name a ``Totals`` that its whole
tree also adds its span time and counters to: that is how one engine
keeps its own sums while the ring forgets old steps. With ``enabled``
False, ``span`` returns one shared null context and ``count`` and
``note`` return at once. Spans open and close on one thread, as the
engine steps.
"""
from __future__ import annotations

import contextlib
import time
import types
from collections import deque
from typing import Any, Dict, Iterator, List, Mapping, Optional, Sequence

import jax
from jax.profiler import TraceAnnotation

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_NULL = contextlib.nullcontext()
# what a span that is not an open root holds for its counters and notes
_EMPTY = types.MappingProxyType({})


class Totals:
    """Cumulative span nanoseconds and counter values, by name."""

    __slots__ = ("ns", "counts")

    def __init__(self):
        self.ns: Dict[str, int] = {}
        self.counts: Dict[str, int] = {}

    def seconds(self, *names: str) -> float:
        return sum(self.ns.get(n, 0) for n in names) * 1e-9


class Span:
    """One timed interval; a root also holds the counters and notes of
    its tree. Entering it opens it on its recorder."""

    __slots__ = ("name", "t0", "t1", "parent", "rid", "attrs", "children",
                 "counts", "notes", "totals", "_rec", "_ann")

    def __init__(self, rec: Optional["Recorder"], name: str, rid: int = -1,
                 attrs: Optional[Dict[str, Any]] = None,
                 totals: Optional[Totals] = None):
        self.name, self.rid, self.attrs = name, rid, attrs or {}
        self.t0 = self.t1 = 0
        self.parent: Optional[Span] = None
        self.children: Sequence[Span] = ()
        self.counts: Mapping[str, int] = _EMPTY
        self.notes: Mapping[str, list] = _EMPTY
        self.totals = totals
        self._rec, self._ann = rec, None

    @property
    def ns(self) -> int:
        return self.t1 - self.t0

    @property
    def self_ns(self) -> int:
        """Time in this span outside its children."""
        return self.ns - sum(c.ns for c in self.children)

    def walk(self) -> Iterator["Span"]:
        """This span and every span under it, depth first."""
        yield self
        for c in self.children:
            yield from c.walk()

    def __enter__(self) -> "Span":
        self._rec._open(self)
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self.t0 = self._rec.clock()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = self._rec.clock()
        self._ann.__exit__(*exc)
        self._ann = None
        self._rec._close(self)
        return False

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, {self.ns} ns, rid={self.rid}, "
                f"{len(self.children)} children)")


class Recorder:
    """A bounded ring of finished root spans, the open spans, and the
    process's cumulative totals."""

    def __init__(self, capacity: int = 4096, clock=time.perf_counter_ns):
        self.enabled = True
        self.clock = clock
        self.roots: deque = deque(maxlen=capacity)
        self.totals = Totals()
        self._stack: List[Span] = []
        self._listening = False

    def clear(self) -> None:
        """Forget the finished spans and the totals."""
        self.roots.clear()
        self.totals = Totals()

    def span(self, name: str, rid: int = -1, totals: Optional[Totals] = None,
             **attrs):
        """A context manager that records ``name`` around its body.
        ``totals``: where this span's tree also adds up (inherited from
        the parent when not given)."""
        if not self.enabled:
            return _NULL
        if not self._listening:
            jax.monitoring.register_event_duration_secs_listener(
                self._on_event)
            self._listening = True
        return Span(self, name, rid, attrs, totals)

    def count(self, name: str, n: int = 1) -> None:
        """Add ``n`` to the counter ``name``: in the cumulative totals,
        the open tree's totals and the open root's record."""
        if not self.enabled:
            return
        _add(self.totals.counts, name, n)
        if self._stack:
            sink = self._stack[-1].totals
            if sink is not None:
                _add(sink.counts, name, n)
            _add(self._stack[0].counts, name, n)

    def note(self, name: str, entry) -> None:
        """Append ``entry`` to the list ``name`` of the open root."""
        if self.enabled and self._stack:
            self._stack[0].notes.setdefault(name, []).append(entry)

    def add(self, name: str, t0_ns: int, t1_ns: int, rid: int = -1,
            **attrs) -> None:
        """Record a root span whose ends were timed elsewhere."""
        if not self.enabled:
            return
        sp = Span(None, name, rid, attrs)
        sp.t0, sp.t1 = int(t0_ns), int(t1_ns)
        self._finish(sp, None)

    def records(self, name: str, lo_ns: float = float("-inf"),
                hi_ns: float = float("inf")) -> List[Span]:
        """The kept root spans called ``name`` that begin in [lo, hi]."""
        return [r for r in list(self.roots)
                if r.name == name and lo_ns <= r.t0 <= hi_ns]

    # ----------------------------------------------------------- internal

    def _open(self, sp: Span) -> None:
        if self._stack:
            sp.parent = self._stack[-1]
            if sp.totals is None:
                sp.totals = sp.parent.totals
        else:
            sp.counts, sp.notes = {}, {}
        self._stack.append(sp)

    def _close(self, sp: Span) -> None:
        # an exception may leave inner spans open: close up to ``sp``
        while self._stack and self._stack.pop() is not sp:
            pass
        self._finish(sp, sp.parent)

    def _finish(self, sp: Span, parent: Optional[Span]) -> None:
        _add(self.totals.ns, sp.name, sp.ns)
        if sp.totals is not None:
            _add(sp.totals.ns, sp.name, sp.ns)
        if parent is None:
            self.roots.append(sp)
        elif parent.children:
            parent.children.append(sp)
        else:
            parent.children = [sp]

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if event != COMPILE_EVENT or not self.enabled:
            return
        self.count("compiles")
        if not self._stack:
            return
        parent = self._stack[-1]
        sp = Span(None, "compile", attrs={"fun": kw.get("fun_name", "")},
                  totals=parent.totals)
        sp.t1 = self.clock()
        sp.t0 = max(parent.t0, sp.t1 - int(duration * 1e9))
        sp.parent = parent
        self._finish(sp, parent)


def _add(d: Dict[str, int], key: str, n: int) -> None:
    d[key] = d.get(key, 0) + n


RECORDER = Recorder()
span, count, note, add = (RECORDER.span, RECORDER.count, RECORDER.note,
                          RECORDER.add)
