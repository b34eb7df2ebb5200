"""Telemetry for the integration service: tracing, metrics, convergence.

One :class:`Observability` object threads through the whole service
stack (engine -> batcher -> cache -> store) and bundles the four
telemetry channels:

* ``tracer``       — wave-pipeline span/instant events
  (:mod:`repro.obs.trace`, Chrome-trace/Perfetto JSONL);
* ``metrics``      — the counter/gauge/histogram registry with
  Prometheus text + JSON expositions (:mod:`repro.obs.metrics`);
* ``convergence``  — per-stream stderr-vs-rounds trajectories
  (:mod:`repro.obs.convergence`);
* ``clock``        — the single wall-clock shim every service-layer
  timestamp goes through (:mod:`repro.obs.clock`, rule OBS001).

``Observability.disabled()`` (the engine default) carries the null
tracer and skips convergence recording; metric objects still exist so
call sites never branch, and the whole disabled path costs a few dict
lookups and locked adds per *wave*, and reads no clock for spans,
parts or lock waits.  Measured on one TPU v5e (51-s benchmark runs,
``bench/``): with tracing off the benchmark's end-to-end metrics moved
within their run-to-run spread; with tracing on (spans, parts and the
profiler's annotations) the VEGAS cell served 18% fewer requests than
untraced (153 against 187) and the paper cell 2.7% fewer (248 against
255), and the request-path spans themselves cost no measurable share.

Construction is cheap and side-effect free; sinks (trace file, metrics
port) attach at the edges (``serve_integrals`` flags, bench phases).
"""

from __future__ import annotations

import weakref

from repro.obs import clock
from repro.obs.convergence import ConvergenceLog, TrajectoryPoint
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               service_metrics)
from repro.obs.trace import (STAGES, JsonlWriter, NullTracer, Tracer,
                             current_part, load_trace, span_totals)

__all__ = [
    "Observability", "ConvergenceLog", "TrajectoryPoint",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "service_metrics",
    "STAGES", "JsonlWriter", "NullTracer", "Tracer", "load_trace",
    "span_totals", "clock",
]


# jax.monitoring's compile-phase events, by the phase they time
COMPILE_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}


class _CompileListener:
    """A ``jax.monitoring`` duration listener for one bundle: counts
    backend compiles, times every phase, and, where a part of this
    bundle's trace is open on the compiling thread, records the compile
    as a ``compile`` part of that part's span, labelled with the part's
    first label (a dispatch's bucket) or its name, then the phase.  It
    holds its bundle weakly, so a bundle never closed costs a dead
    call."""

    def __init__(self, obs: "Observability"):
        self._obs = weakref.ref(obs)

    def __call__(self, event: str, duration: float, **kw) -> None:
        phase = COMPILE_PHASES.get(event)
        obs = self._obs() if phase is not None else None
        if obs is None:
            return
        obs.m["compile_seconds"].observe(duration, phase=phase)
        if phase == "backend":
            obs.m["backend_compiles"].inc()
        part = current_part()
        if part is not None and part.span.tracer is obs.tracer:
            t1 = clock.monotonic_ns()
            label = part.labels[0] if part.labels else part.name
            part.span.add_part("compile", t1 - int(duration * 1e9), t1,
                               label, phase)


class Observability:
    """The telemetry bundle the engine threads through the stack."""

    def __init__(self, *, tracer=None, metrics: MetricsRegistry | None = None,
                 convergence: ConvergenceLog | None = None,
                 record_convergence: bool = True):
        from repro.obs.trace import NULL
        self.tracer = tracer if tracer is not None else NULL
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.convergence = (convergence if convergence is not None
                            else ConvergenceLog())
        self.record_convergence = bool(record_convergence)
        # the canonical service metric handles, pre-resolved so hot
        # paths never pay the registry lookup
        self.m = service_metrics(self.metrics)
        if self.tracer.enabled:
            # spans already time the stages; mirror their durations into
            # the per-stage latency histogram so the Prometheus
            # exposition and the trace artifact can never disagree
            stage_hist = self.m["stage_seconds"]

            def _stage_sink(ev: dict) -> None:
                if ev.get("ph") == "X" and ev["name"] in STAGES:
                    stage_hist.observe(ev["dur"] / 1e6, stage=ev["name"])

            self.tracer.add_sink(_stage_sink)
        # compiles are counted with tracing off too; close() removes it
        self._compile_listener = None
        try:
            import jax.monitoring
        except ImportError:         # no JAX: nothing compiles
            return
        self._compile_listener = _CompileListener(self)
        jax.monitoring.register_event_duration_secs_listener(
            self._compile_listener)

    @classmethod
    def disabled(cls) -> "Observability":
        """The default: null tracer, no convergence recording, metrics
        still counted (they are the service's own observables)."""
        return cls(record_convergence=False)

    @classmethod
    def enabled(cls, *, trace_path: str | None = None,
                jax_annotations: bool = False,
                sinks=(), max_trajectory_points: int = 512
                ) -> "Observability":
        """Full telemetry: tracing (to ``trace_path`` and/or extra
        ``sinks``), metrics, convergence accounting."""
        all_sinks = list(sinks)
        if trace_path is not None:
            all_sinks.append(JsonlWriter(trace_path))
        tracer = Tracer(*all_sinks, jax_annotations=jax_annotations)
        return cls(tracer=tracer,
                   convergence=ConvergenceLog(max_trajectory_points),
                   record_convergence=True)

    @property
    def tracing(self) -> bool:
        return self.tracer.enabled

    def span(self, name: str, **args):
        return self.tracer.span(name, **args)

    def event(self, name: str, **args) -> None:
        self.tracer.instant(name, **args)

    def wave(self, seq: int):
        return self.tracer.wave(seq)

    def complete(self, name: str, t0_ns: int, t1_ns: int,
                 tid: int | None = None, **args) -> None:
        self.tracer.complete(name, t0_ns, t1_ns, tid, **args)

    def close(self) -> None:
        """Remove the compile listener and close the trace sinks
        (idempotent)."""
        listener, self._compile_listener = self._compile_listener, None
        if listener is not None:
            import jax.monitoring
            jax.monitoring.unregister_event_duration_listener(listener)
        self.tracer.close()
