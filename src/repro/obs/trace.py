"""Phase spans recorded into a bounded ring buffer, exportable as
Chrome/Perfetto ``trace_event`` JSON.

A span times a *host-side* phase of the serving loop::

    with tracer.span("dispatch", kind="insert", bucket=64):
        ...               # the jitted round is dispatched here

Spans never block on device values — what they measure is the host
wall-clock of the phase (for an async dispatch that is the enqueue
cost; the blocking ``flag_readback`` span absorbs the device time), so
tracing respects the one-readback-per-round invariant by construction.

An interval known only afterwards (a request's wait in the queue) is
recorded with :meth:`Tracer.record` from its two ``perf_counter_ns``
stamps.  Every span carries an ``id`` and a ``parent``: a live span's
id is its sequence number in the tracer; a recorded interval takes the
id and parent its caller gives (a request's spans share its ticket and
name the round's ``dispatch`` span).

The ring holds the most recent ``capacity`` completed spans as plain
tuples; wraparound overwrites oldest-first, so a long-running server
keeps a bounded trace of its recent rounds.  ``export()`` emits the
standard ``{"traceEvents": [...]}`` JSON object format (``ph: "X"``
complete events, microsecond timestamps) that ``chrome://tracing`` and
https://ui.perfetto.dev load directly; thread-name metadata events
(``ph: "M"``) label each host thread.

Timestamps are on the profiler's host clock: the Unix-epoch clock that
``jax.profiler`` stamps host events with (a ``ProfileData`` event's
``start_ns`` is that clock minus the capture's ``profile_start_time``).
The tracer reads ``perf_counter_ns`` per span and converts with one
anchor taken at construction, so spans recorded after the fact and
device events lie on one timeline.  When the optional
``jax_annotations`` bridge is on, every live span also enters a
``jax.profiler.TraceAnnotation``, its twin in the captured profile, and
the process's compilation cache keys programs on their op metadata, so
the profile's op paths are this source's (``repro.obs.phases``).

:data:`NULL_TRACER` is the disabled twin: ``span()`` returns a shared
no-op context manager — one branch + two empty calls per span, nothing
recorded.
"""
from __future__ import annotations

import itertools
import json
import threading
import time


class _NullSpan:
    __slots__ = ()
    id = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()


class NullTracer:
    """Disabled tracer: every span is the shared no-op singleton."""
    enabled = False
    dropped = 0

    def span(self, name: str, **args):
        return NULL_SPAN

    def events(self) -> list:
        return []

    def export(self) -> dict:
        return {"traceEvents": []}

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.export(), f)


NULL_TRACER = NullTracer()


class _Span:
    __slots__ = ("_tracer", "name", "args", "t0", "_ann", "id")

    def __init__(self, tracer: "Tracer", name: str, args: dict):
        self._tracer = tracer
        self.name = name
        self.args = args
        self._ann = None

    def __enter__(self):
        tr = self._tracer
        if tr._annotate is not None:
            self._ann = tr._annotate(self.name)
            self._ann.__enter__()
        self.id = next(tr._seq)
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self._tracer.record_many(
            ((self.name, self.t0, t1, self.id, None, self.args),))
        return False


def _clock_anchor(tries: int = 5) -> int:
    """Nanoseconds to add to ``perf_counter_ns`` for the Unix-epoch
    clock, from the tightest of a few back-to-back readings."""
    best = None
    for _ in range(tries):
        a = time.perf_counter_ns()
        wall = time.time_ns()
        b = time.perf_counter_ns()
        if best is None or b - a < best[0]:
            best = (b - a, wall - (a + b) // 2)
    return best[1]


class Tracer:
    """Span recorder with a bounded ring buffer (module docstring)."""
    enabled = True

    def __init__(self, capacity: int = 65536, jax_annotations: bool = False):
        assert capacity >= 1
        self._cap = capacity
        self._buf: list = [None] * capacity
        self._n = 0                       # total spans ever recorded
        self._seq = itertools.count(1)    # live span ids
        self._anchor = _clock_anchor()
        self._tids: dict[int, int] = {}
        self._tid_names: dict[int, str] = {}
        self._lock = threading.Lock()
        self._annotate = None
        if jax_annotations:
            import jax
            from jax.profiler import TraceAnnotation
            self._annotate = TraceAnnotation
            # The persistent compilation cache keys programs without
            # their op metadata, so a cached executable would show the
            # named phases of whichever source compiled it first in the
            # captured profile: key on the metadata too (process-wide).
            jax.config.update(
                "jax_compilation_cache_include_metadata_in_key", True)

    def span(self, name: str, **args) -> _Span:
        return _Span(self, name, args)

    def record(self, name: str, t0_ns: int, t1_ns: int, id=None,
               parent=None, **args) -> None:
        """Record a completed interval from two ``perf_counter_ns``
        stamps (module docstring)."""
        self.record_many(((name, t0_ns, t1_ns, id, parent, args),))

    def record_many(self, spans) -> None:
        """:meth:`record` for an iterable of ``(name, t0_ns, t1_ns, id,
        parent, args)``, under one lock: the phases of every request a
        flush answered, known only once it returns, go through here."""
        ident = threading.get_ident()
        with self._lock:
            tid = self._tids.get(ident)
            if tid is None:
                tid = self._tids[ident] = len(self._tids)
                self._tid_names[tid] = threading.current_thread().name
            rows = [(name, t0, t1, tid, args, id_, parent)
                    for name, t0, t1, id_, parent, args in spans]
            cap, n = self._cap, self._n
            for i in range(0, len(rows), cap):      # slices of the ring
                part = rows[i:i + cap]
                at = (n + i) % cap
                head = part[:cap - at]
                self._buf[at:at + len(head)] = head
                self._buf[:len(part) - len(head)] = part[len(head):]
            self._n = n + len(rows)

    # -- extraction ------------------------------------------------------
    @property
    def dropped(self) -> int:
        """Spans overwritten by ring wraparound."""
        return max(0, self._n - self._cap)

    def events(self) -> list:
        """Retained spans oldest-first:
        ``(name, ts_us, dur_us, tid, args, id, parent)`` tuples, ``ts_us``
        on the profiler's host clock (module docstring)."""
        with self._lock:
            n, cap = self._n, self._cap
            if n <= cap:
                raw = self._buf[:n]
            else:
                start = n % cap
                raw = self._buf[start:] + self._buf[:start]
        anchor = self._anchor
        return [(name, (t0 + anchor) // 1000, max(1, (t1 - t0) // 1000),
                 tid, args, id_, parent)
                for name, t0, t1, tid, args, id_, parent in raw]

    def export(self) -> dict:
        """Chrome/Perfetto ``trace_event`` JSON object format."""
        events = []
        for tid, tname in sorted(self._tid_names.items()):
            events.append({"name": "thread_name", "ph": "M", "pid": 0,
                           "tid": tid, "args": {"name": tname}})
        for name, ts, dur, tid, args, id_, parent in self.events():
            ev = {"name": name, "ph": "X", "cat": "pfo", "pid": 0,
                  "tid": tid, "ts": ts, "dur": dur, "id": id_}
            if parent is not None:
                ev["parent"] = parent
            if args:
                ev["args"] = {k: v for k, v in args.items()}
            events.append(ev)
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.export(), f)
