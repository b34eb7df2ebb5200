"""One run of one benchmark cell, from set-up to the result line.

A run builds one ``IntegrationEngine(use_kernel=True)`` for the cell's
configuration, warms every kernel shape the cell's traffic can ask for,
starts the engine's worker and drives it from the traffic mix's clients
through ``submit`` and ``result``.  It measures a window of ``seconds``,
waits for the answers still in flight, reads a durable store back
(``durability.py``), frees the engine, compares every answer due in the
window with the closed form (``check.py``) and with the store, and prints
one JSON line: the end-to-end metrics, or with ``trace`` the per-layer
metrics read from a profiler trace of the window and the service's spans.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
import shutil
import sys
import tempfile
import time

import numpy as np

from bench import check, discover, durability, loadgen, stats, tracefile

PHASE_SHAPES = 2
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Refused(Exception):
    """The run cannot measure what it is asked to: no result is printed."""


@dataclasses.dataclass
class Context:
    """What a per-layer reader may read, all of it from the window."""
    workload: str
    config: dict
    window_s: float
    records: list          # every request submitted in the window
    completed: list        # the records answered inside the window
    spans: list            # service spans begun in the window
    counters: dict         # unlabelled zmc_* counters, change over window
    compiles: int          # backend compiles inside the window
    trace: dict | None     # tracefile.load() of the window


def _merge(base: dict, over: dict | None) -> dict:
    out = dict(base)
    for k, v in (over or {}).items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) else v
    return out


class CompileLog:
    """Times at which JAX finished a backend compile, and its persistent
    cache's hits and writes."""

    def __init__(self, clock):
        self.clock = clock
        self.times: list[float] = []
        self.seconds = 0.0
        self.long = 0      # compiles of a second or more
        self.hits = self.writes = 0

    def __call__(self, event: str, duration: float, **kw) -> None:
        if event == COMPILE_EVENT:
            self.times.append(self.clock())
            self.seconds += duration
            self.long += duration >= 1.0

    def count(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.times if t0 <= t <= t1)


def _device(devs) -> dict:
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def _counters(engine) -> dict:
    snap = engine.obs.metrics.snapshot()
    return {name: m["value"] for name, m in snap.items()
            if m["type"] == "counter" and isinstance(m["value"], (int, float))}


def _memory_peak(devs) -> int:
    peak = 0
    for d in devs:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return peak


class Cell:
    """The configuration's requests and engine, built from its JSON."""

    def __init__(self, config: dict, seed: int):
        self.config = config
        self.request = config["request"]
        self.seed = int(seed)
        self.form = discover.form(self.request["form"])
        eng = config["engine"]
        self.round_samples = int(eng["round_samples"])
        self.max_rounds = int(eng["max_rounds_per_wave"])

    def draw(self, phase: int, *ids: int) -> dict:
        rng = np.random.default_rng(
            np.random.SeedSequence([self.seed, phase, *ids]))
        return self.form.draw(rng, self.request)

    def make(self, params: dict, *, n_samples: int | None = None,
             edges=None):
        """The request for ``params``; ``n_samples`` with ``edges`` makes a
        fixed-budget request on an already adapted family."""
        from repro.service import IntegrationRequest
        fam = self.form.family(params, self.request)
        req = self.request
        if edges is not None:
            return IntegrationRequest.make(
                [fam.adapted(edges)], n_samples=n_samples,
                sampler=req["sampler"])
        return IntegrationRequest.make(
            [fam], n_samples=n_samples or req.get("n_samples"),
            target_stderr=req.get("target_stderr"),
            adaptive=bool(req.get("adaptive")), sampler=req["sampler"])

    def engine(self, obs, state_dir):
        from repro.service import IntegrationEngine
        eng = self.config["engine"]
        mesh = None
        if eng.get("model_parallel"):
            from repro.launch.mesh import make_mesh_for
            mesh = make_mesh_for(model_parallel=int(eng["model_parallel"]))
        return IntegrationEngine(
            seed=self.seed, round_samples=self.round_samples,
            use_kernel=True, mesh=mesh,
            max_rounds_per_wave=self.max_rounds, state_dir=state_dir,
            store_fsync=True, obs=obs)

    def wave_rounds(self) -> list[int]:
        """Round counts a stream of this configuration can have in a wave:
        any up to the wave's cap when adaptation picks them, else the
        budget's split into waves."""
        if self.request.get("adaptive"):
            return list(range(1, self.max_rounds + 1))
        n = math.ceil(int(self.request["n_samples"]) / self.round_samples)
        sizes = {min(n, self.max_rounds)}
        if n > self.max_rounds and n % self.max_rounds:
            sizes.add(n % self.max_rounds)
        return sorted(sizes)

    def warm_shapes(self, engine, clients: int) -> None:
        """One synchronous wave for each number of streams (1..clients) and
        each round count the traffic can put in a wave, so that the window
        finds every kernel shape compiled."""
        adaptive = bool(self.request.get("adaptive"))
        for k in range(1, clients + 1):
            for r in self.wave_rounds():
                reqs = []
                for j in range(k):
                    params = self.draw(PHASE_SHAPES, k, r, j)
                    edges = None
                    if adaptive:
                        from repro.core import adaptive as adapt
                        box = np.asarray(
                            self.form.family(params, self.request).domains)
                        edges = adapt.initial_edges(box, engine.adapt_bins)
                    reqs.append(self.make(
                        params, n_samples=r * self.round_samples,
                        edges=edges))
                tickets = [engine.submit(q) for q in reqs]
                while engine.step():
                    pass
                for t in tickets:
                    res = engine.poll(t)
                    if res is None or res.failed:
                        raise RuntimeError(
                            f"warm-up wave ({k} streams x {r} rounds) "
                            f"was not served: {res}")


def _end_to_end(name: str, t_start: float, t0: float, t1: float,
                records: list, completed: list) -> float | None:
    if name == "setup_s":
        return t0 - t_start
    if name == "fn_samples_per_s":
        # every request sent in the window, over the time until the last
        # came back: no request is cut at the close
        return stats.rate((r.result.n_fn_total * float(np.mean(
            r.result.n_per_family)) for r in records if r.ok),
            stats.drain_end(records, t1) - t0)
    lat = [r.done_t - r.submit_t for r in completed]
    if not lat:
        return None
    if name == "result_s_p50":
        return stats.percentile(lat, 50)
    if name == "result_s_p95":
        return stats.percentile(lat, 95)
    raise KeyError(f"end-to-end metric {name!r} is not one the harness "
                   "measures")


def _finite(x):
    return x if isinstance(x, (int, str)) or math.isfinite(x) else repr(x)


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        require_tpu: bool = True, overrides: dict | None = None,
        late_s: float = 60.0, out=None, err=None) -> int:
    """Run one cell and print its result line; returns the exit code.

    ``require_tpu=False`` and ``overrides`` (merged into the configuration
    and the mix, ``{"config": {...}, "traffic": {...}}``) let tests drive
    the same code at small sizes on the CPU; the command line never sets
    them.
    """
    out = out or sys.stdout
    err = err or sys.stderr
    clock = time.monotonic
    t_start = clock()
    bench = discover.load_benchmark()
    cell = discover.workload(bench, workload)
    overrides = overrides or {}
    config = _merge(discover.config(cell["config"]),
                    overrides.get("config"))
    mix = _merge(discover.traffic(cell["traffic"]), overrides.get("traffic"))
    reported = discover.cell_metrics(bench, workload, trace)

    if require_tpu:
        # the compile cache lives in the checkout, at a fixed path
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(discover.ROOT
                                                      / ".jax_cache")
    import jax
    import jax.monitoring

    if require_tpu:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
    from repro.kernels.pallas_compat import should_interpret
    from repro.obs import Observability

    devs = jax.devices()
    device = _device(devs)
    if require_tpu:
        if device["platform"] != "tpu":
            raise Refused(f"JAX found no TPU (platform "
                          f"{device['platform']!r})")
        if device["count"] < int(cell["chips"]):
            raise Refused(f"cell needs {cell['chips']} chips; JAX has "
                          f"{device['count']}")
        if should_interpret():
            raise Refused("the kernels would run interpreted")

    compiles = CompileLog(clock)
    jax.monitoring.register_event_duration_secs_listener(compiles)
    jax.monitoring.register_event_listener(compiles.count)
    t_devices = clock()
    spans: list[dict] = []
    obs = (Observability.enabled(sinks=[spans.append], jax_annotations=True)
           if trace else Observability.disabled())
    c = Cell(config, seed)
    reference = discover.reference(c.request["form"])
    state_dir = (tempfile.mkdtemp(prefix="zmc_bench_state_")
                 if config["engine"].get("durable_store") else None)
    trace_dir = tempfile.mkdtemp(prefix="zmc_bench_trace_") if trace else None
    syncs = durability.SyncLog(state_dir, clock) if state_dir else None
    durable = None
    window = {}
    try:
        if syncs:
            syncs.install()
        engine = c.engine(obs, state_dir)
        try:
            c.warm_shapes(engine, int(mix["clients"]))
            t_shapes = clock()
            engine.start()

            def serve(params):
                # a warm-up request of a cold first run waits on compiles
                return engine.result(engine.submit(c.make(params)),
                                     timeout=seconds + late_s + 1200.0)

            def start_window():
                window["c0"] = _counters(engine)
                if trace:
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0
                    opts.host_tracer_level = 1   # annotations, not runtime
                    jax.profiler.start_trace(trace_dir,
                                             profiler_options=opts)
                window["tw0"] = clock()

            def end_window():
                window["tw1"] = clock()
                window["c1"] = _counters(engine)
                if trace:
                    jax.profiler.stop_trace()

            t0, t1, records = loadgen.closed_loop(
                serve, c.draw, mix, seconds, on_window_start=start_window,
                on_window_end=end_window, late_s=late_s, clock=clock)
            device["memory_peak_bytes"] = _memory_peak(devs)
            if state_dir:
                # every answer is in; the engine has not yet compacted
                durable = durability.read_state(state_dir)
        finally:
            engine.close()
        del engine
        tdata = None
        path = tracefile.find_xplane(trace_dir) if trace else None
        if path is not None:
            tdata = tracefile.load(
                path, int((window["tw1"] - window["tw0"]) * 1e9))
    finally:
        if syncs:
            syncs.remove()
        jax.monitoring.unregister_event_duration_listener(compiles)
        jax.monitoring.unregister_event_listener(compiles.count)
        for d in (state_dir, trace_dir):
            if d:
                shutil.rmtree(d, ignore_errors=True)

    print(f"setup: {t_devices - t_start:.3f} s to the devices, "
          f"{t_shapes - t_devices:.3f} s engine and shape warm-up, "
          f"{t0 - t_shapes:.3f} s warm-up traffic; "
          f"{compiles.between(t_start, t0)} backend compiles "
          f"({compiles.seconds:.3f} s in all, {compiles.long} of 1 s or "
          f"more), {compiles.hits} persistent "
          f"cache hits, {compiles.writes} cache writes", file=err)
    completed = stats.completed_in(records, t0, t1)
    metrics = {}
    breakdown = None
    if not trace:
        for m in reported:
            v = _end_to_end(m["name"], t_start, t0, t1, records,
                            completed)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        us0, us1 = t0 * 1e6, t1 * 1e6
        ctx = Context(
            workload=workload, config=config, window_s=t1 - t0,
            records=records, completed=completed,
            spans=[s for s in spans if s.get("ph") == "X"
                   and us0 <= s["ts"] <= us1],
            counters={n: v - window["c0"].get(n, 0)
                      for n, v in window["c1"].items()},
            compiles=compiles.between(t0, t1), trace=tdata)
        for m in reported:
            v = discover.metric_reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if tdata is not None and tdata["devices"]:
            device["busy_s"] = tracefile.busy_s(tdata)
            device["window_s"] = tdata["window_ns"] / 1e9
            breakdown = {"device_ops": tracefile.top_ops(tdata),
                         "idle_gaps": tracefile.gaps_by_host_stage(tdata)}

    answers = [(r.params, r.result if r.ok else None) for r in records]
    numbers = check.compare(answers, c.request, reference, c.round_samples,
                            config["check"])
    if durable is not None:
        numbers.update(durability.compare(records, durable, syncs.events))
    correct = check.passed(numbers)
    line = {"correct": correct, "attempted": len(records),
            "failed": sum(1 for r in records if not r.ok),
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["check"] = {k: {"value": _finite(v["value"]), "limit": v["limit"]}
                     for k, v in numbers.items()}
    for text in check.lines(numbers):
        print(text, file=err)
    print(json.dumps(line), file=out, flush=True)
    return 0
