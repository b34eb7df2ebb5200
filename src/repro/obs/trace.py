"""Span tracing for the wave pipeline, Chrome-trace/Perfetto format.

A :class:`Tracer` turns ``with tracer.span("launch", wave=3):`` into a
complete-duration event (``ph: "X"``) and ``tracer.instant(...)`` into
an instant event (``ph: "i"``), both in the Trace Event Format that
``chrome://tracing`` and https://ui.perfetto.dev load directly.  Events
flow to pluggable sinks:

* :class:`JsonlWriter` — the on-disk artifact: one event object per
  line.  The file opens with ``[`` and each event line ends with a
  comma; the Trace Event spec makes the closing ``]`` optional, so a
  crash mid-run still leaves a loadable trace (and CI can upload it
  verbatim).  :func:`load_trace` parses one back for assertions.
* any callable ``sink(event_dict)`` — tests collect into a list.

The six pipeline stages the engine instruments are named in
:data:`STAGES`; the acceptance gate asserts a served workload's trace
covers all six.  With ``jax_annotations=True`` every span additionally
enters a ``jax.profiler.TraceAnnotation`` so the same stage names line
up inside a device profile (XProf/TensorBoard) — lazily imported and
silently skipped where unavailable.

A span can carry **parts**: timings inside it kept as an arg of the
span (``args["parts"]``, each ``[name, offset_us, dur_us, *labels]``
with the offset from the span's start), not as child spans, so a
stage's self time is what it was.  ``with span.part("dispatch",
bucket):`` times one; :func:`current_part` names the part open on the
calling thread, which is how a compile JAX reports during a dispatch
becomes a ``compile`` part of that dispatch's span.  Inside ``with
tracer.wave(seq):`` every span the thread opens carries ``wave=seq``.
:meth:`Tracer.complete` emits a span after the fact, on any thread's
tid (a request's submit-to-finish span, stamped on the client's).

When tracing is off the engine holds the module-level :data:`NULL`
tracer: ``span()`` returns one shared no-op context manager (whose
``part()`` is itself), so the disabled hot path costs two attribute
lookups per stage per wave and reads no clock.

Timestamps come from :mod:`repro.obs.clock` (monotonic ns -> trace µs)
— never from ``time`` directly (rule OBS001).
"""

from __future__ import annotations

import json
import os
import threading

from repro.obs import clock

# The wave-pipeline stages engine/batcher/store instrument, in causal
# order.  plan: the fair round-robin budget split.  launch: fused
# pallas_call dispatch (async — returns device futures).  device_execute:
# blocking until the device finishes the wave.  transfer: materializing
# sums on host.  deposit: cache fold + request completion.  wal_commit:
# the group-committed journal write+fsync.
STAGES = ("plan", "launch", "device_execute", "transfer", "deposit",
          "wal_commit")


def current_tid() -> int:
    """The calling thread's id as trace events carry it."""
    return threading.get_ident() & 0xFFFF


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def part(self, name: str, *labels):
        return self

    def set(self, **args) -> None:
        return None


_NULL_SPAN = _NullSpan()


class NullTracer:
    """The disabled tracer: every operation is a shared no-op."""

    enabled = False

    def span(self, name: str, **args):
        return _NULL_SPAN

    def instant(self, name: str, **args) -> None:
        return None

    def complete(self, name: str, t0_ns: int, t1_ns: int,
                 tid: int | None = None, **args) -> None:
        return None

    def wave(self, seq: int):
        return _NULL_SPAN

    def flush(self) -> None:
        return None

    def close(self) -> None:
        return None


NULL = NullTracer()

# per thread: the parts open, innermost last, and the wave bound
_OPEN = threading.local()


class _Wave:
    __slots__ = ("seq", "prev")

    def __init__(self, seq: int):
        self.seq = seq

    def __enter__(self):
        self.prev = getattr(_OPEN, "wave", None)
        _OPEN.wave = self.seq
        return self

    def __exit__(self, *exc):
        _OPEN.wave = self.prev
        return False


def current_part() -> "_Part | None":
    """The innermost part open on the calling thread, or None."""
    stack = getattr(_OPEN, "parts", None)
    return stack[-1] if stack else None


class _Part:
    __slots__ = ("span", "name", "labels", "t0")

    def __init__(self, span: "_Span", name: str, labels: tuple):
        self.span = span
        self.name = name
        self.labels = labels

    def __enter__(self):
        stack = getattr(_OPEN, "parts", None)
        if stack is None:
            stack = _OPEN.parts = []
        stack.append(self)
        self.t0 = clock.monotonic_ns()
        return self

    def __exit__(self, *exc):
        t1 = clock.monotonic_ns()
        _OPEN.parts.pop()
        self.span.add_part(self.name, self.t0, t1, *self.labels)
        return False


class _Span:
    __slots__ = ("tracer", "name", "args", "t0", "annotation", "parts")

    def __init__(self, tracer: "Tracer", name: str, args: dict):
        self.tracer = tracer
        self.name = name
        self.args = args
        self.annotation = None
        self.parts = None

    def part(self, name: str, *labels) -> _Part:
        """Context manager timing a part of this (open) span."""
        return _Part(self, name, labels)

    def add_part(self, name: str, t0_ns: int, t1_ns: int, *labels) -> None:
        """Record a part that ran from ``t0_ns`` to ``t1_ns``."""
        if self.parts is None:
            self.parts = []
        self.parts.append([name, (t0_ns - self.t0) // 1000,
                           max(t1_ns - t0_ns, 0) // 1000, *labels])

    def set(self, **args) -> None:
        """Add args known only once the span is open (a ticket)."""
        self.args.update(args)

    def __enter__(self):
        wave = getattr(_OPEN, "wave", None)
        if wave is not None:
            self.args.setdefault("wave", wave)
        self.t0 = clock.monotonic_ns()
        ann = self.tracer._annotation
        if ann is not None:
            self.annotation = ann(self.name)
            self.annotation.__enter__()
        return self

    def __exit__(self, *exc):
        if self.annotation is not None:
            self.annotation.__exit__(*exc)
        t1 = clock.monotonic_ns()
        if self.parts is not None:
            self.args["parts"] = self.parts
        self.tracer._complete(self.name, self.t0, t1, current_tid(),
                              self.args)
        return False


class Tracer:
    """Emits trace events to sinks; enabled iff it has at least one."""

    enabled = True

    def __init__(self, *sinks, jax_annotations: bool = False):
        self.pid = os.getpid()
        self._sinks = list(sinks)
        self._annotation = None
        if jax_annotations:
            try:
                from jax.profiler import TraceAnnotation
                self._annotation = TraceAnnotation
            except Exception:       # profiler moved / absent: trace anyway
                self._annotation = None

    def add_sink(self, sink) -> None:
        self._sinks.append(sink)

    def span(self, name: str, **args) -> _Span:
        """Context manager timing one pipeline stage."""
        return _Span(self, name, args)

    def wave(self, seq: int) -> _Wave:
        """Context manager: spans the calling thread opens inside it
        carry ``wave=seq`` (unless given one), however deep the call
        that opens them (the store's ``wal_commit``)."""
        return _Wave(seq)

    def complete(self, name: str, t0_ns: int, t1_ns: int,
                 tid: int | None = None, **args) -> None:
        """Emit a span that ran from ``t0_ns`` to ``t1_ns`` (monotonic
        ns), after the fact, on ``tid`` (default: the calling thread)."""
        self._complete(name, t0_ns, t1_ns,
                       current_tid() if tid is None else tid, args)

    def _complete(self, name: str, t0_ns: int, t1_ns: int, tid: int,
                  args: dict) -> None:
        self._emit({
            "ph": "X", "name": name, "cat": "wave",
            "ts": t0_ns // 1000, "dur": max((t1_ns - t0_ns) // 1000, 1),
            "pid": self.pid, "tid": tid, "args": args,
        })

    def instant(self, name: str, **args) -> None:
        """A point event (failure paths: restarts, stragglers, torn
        commits) carrying stream/wave identity in ``args``."""
        self._emit({
            "ph": "i", "name": name, "cat": "event", "s": "t",
            "ts": clock.monotonic_ns() // 1000,
            "pid": self.pid, "tid": current_tid(),
            "args": args,
        })

    def _emit(self, event: dict) -> None:
        for sink in self._sinks:
            sink(event)

    def flush(self) -> None:
        for sink in self._sinks:
            if hasattr(sink, "flush"):
                sink.flush()

    def close(self) -> None:
        for sink in self._sinks:
            if hasattr(sink, "close"):
                sink.close()


class JsonlWriter:
    """Trace sink writing the crash-tolerant headless-array JSONL file."""

    def __init__(self, path: str):
        self.path = str(path)
        self._lock = threading.Lock()
        self._f = open(self.path, "w", encoding="utf-8")
        self._f.write("[\n")
        self.n_events = 0

    def __call__(self, event: dict) -> None:
        line = json.dumps(event, sort_keys=True,
                          separators=(",", ":")) + ",\n"
        with self._lock:
            self._f.write(line)
            self.n_events += 1

    def flush(self) -> None:
        with self._lock:
            if not self._f.closed:
                self._f.flush()

    def close(self) -> None:
        with self._lock:
            if not self._f.closed:
                self._f.flush()
                self._f.close()


def load_trace(path: str) -> list[dict]:
    """Parse a :class:`JsonlWriter` artifact (or any Trace Event JSON
    array, trailing-comma/unclosed included) back into event dicts."""
    with open(path, encoding="utf-8") as f:
        text = f.read().strip()
    if text.startswith("["):
        text = text[1:]
    text = text.rstrip("]").rstrip().rstrip(",")
    if not text:
        return []
    return json.loads(f"[{text}]")


def span_totals(events: list[dict]) -> dict[str, float]:
    """Total seconds per span name over a parsed trace (``ph == "X"``).

    The host-per-wave bench phase aggregates with this; dur is µs."""
    totals: dict[str, float] = {}
    for ev in events:
        if ev.get("ph") == "X":
            totals[ev["name"]] = (totals.get(ev["name"], 0.0)
                                  + ev.get("dur", 0) / 1e6)
    return totals
