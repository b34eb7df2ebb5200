"""The continuously-batching integration engine (submit/poll worker).

Life of a request:

1. **submit** — each family is canonicalized and content-hashed
   (:mod:`repro.service.canonical`); the hash (plus sampler) addresses a
   :class:`~repro.service.cache.CacheEntry`, allocated on first sight
   with its own counter-space range.  If every entry already meets the
   requested precision the result is finalized immediately — a pure
   cache hit, zero launches.  Otherwise the request parks in the pending
   table (bounded: submits beyond ``max_pending`` block, or raise
   :class:`~repro.service.api.Backpressure` when non-blocking).

2. **wave** (``step``) — the engine sweeps the pending table, asks the
   cache how many more rounds each entry needs beyond its fold frontier
   *plus whatever is already in flight*, and assigns the wave's round
   budget **fairly**: requests are visited round-robin (one round per
   stream per pass, rotating the starting request every wave), so when
   ``max_items_per_wave`` bounds the wave, a heavy precision ask can
   never starve a small latency-sensitive one.  The
   :class:`~repro.service.batcher.RoundBatcher` coalesces the wave into
   fused multi-round dimension-bucket launches (an R-round wave over B
   buckets costs B ``pallas_call``\\ s).  Each wave runs under the
   :class:`~repro.distributed.fault_tolerance.StepWatchdog` and inside
   :func:`~repro.distributed.fault_tolerance.run_with_restarts`: because
   work is counter-addressed and deposits happen only at wave end, a
   crashed wave replays identically.

   The background worker **pipelines** waves (double buffering): wave
   k+1's device work is dispatched while wave k's results transfer and
   group-commit on the host, keeping deposits and WAL journaling off the
   device critical path (``pipeline_waves=False`` restores strictly
   serial waves).  In-flight rounds are tracked per stream so the
   planner schedules beyond them instead of re-planning them.

2b. **adapt** (opt-in) — a request with ``adaptive=True`` and a stderr
   target samples through a VEGAS importance grid
   (:mod:`repro.core.adaptive`, ``docs/adaptive.md``): epoch 1 is fit
   at submit from a deterministic counter-keyed pilot, and the planner
   refits between waves while the target is unmet.  Every epoch is a
   NEW cache stream keyed by its grid's edges (the grid record is
   journaled *before* the child's alloc — the Layer-3 STR007 chain),
   so adapted streams keep the bit-identical resume contract: a
   restarted engine adopts the journaled chain tip instead of
   refitting.

3. **complete** — requests whose entries all meet their precision are
   finalized from the cache accumulators and their tickets released.

``start()`` spawns the worker thread for async submit/poll service;
``step()`` drives the same loop synchronously (tests, batch jobs).

With a ``state_dir``, the cache journals every deposit through a
:class:`~repro.service.store.DurableStore` (replayed on boot, corrupt
tails truncated) and ``stop()``/``close()`` snapshot-compact on
shutdown — so a SIGKILLed engine restarts warm: already-satisfied
requests cost zero launches and partially-met ones top up from their
persisted ``sample_offset`` bit-identically to an uninterrupted run.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import zlib
from typing import Sequence

import numpy as np

from repro.analysis import streams as _analysis
from repro.core import adaptive
from repro.core import rng as rng_lib
from repro.obs import Observability
from repro.obs import clock as _clock
from repro.obs.trace import current_tid
from repro.service.api import (Backpressure, IntegrationRequest,
                               IntegrationResult, RequestFailed,
                               SweepRequest, SweepResult)
from repro.service.batcher import InFlightWave, RoundBatcher, WorkItem
from repro.service.cache import CacheEntry, ResultCache
from repro.service.canonical import (DEFAULT_SWEEP_SLICE, canonical_family,
                                     family_hash, sweep_slices)
from repro.service.faults import NULL_FAULTS, InjectedCrash
from repro.service.resilience import (Deadline, DeadlineExceeded,
                                      RetryExhausted, RetryPolicy,
                                      StepWatchdog, run_with_policy)
from repro.service.store import DurableStore


def _wave_streams(items: Sequence[WorkItem]) -> list[str]:
    """Stable, deduplicated stream-id prefixes for event payloads."""
    seen: list[str] = []
    for it in items:
        sid = it.chash[:16]
        if sid not in seen:
            seen.append(sid)
    return seen


class _Acquire:
    """``with _Acquire(lock, timer):`` holds ``lock``, the wait for it
    timed by ``timer`` (a trace span or part, closed once the lock is
    held; the shared no-op when tracing is off)."""

    __slots__ = ("lock", "timer")

    def __init__(self, lock, timer):
        self.lock = lock
        self.timer = timer

    def __enter__(self):
        with self.timer:
            self.lock.acquire()
        return self

    def __exit__(self, *exc):
        self.lock.release()
        return False


@dataclasses.dataclass
class _TicketTrace:
    """What a ticket's ``request`` span reports (tracing on only):
    monotonic ns of its submit, admission and first launch, the client
    thread's tid, and the waves and rounds that carried its streams."""

    tid: int
    submit_ns: int
    admit_ns: int
    launch_ns: int | None = None
    waves: int = 0
    rounds: int = 0


@dataclasses.dataclass
class EngineStats:
    submitted: int = 0
    served: int = 0
    cache_hits: int = 0        # requests served with zero new rounds
    waves: int = 0
    items_executed: int = 0
    items_requested: int = 0   # before cross-request dedup
    restarts: int = 0
    failed: int = 0            # tickets completed as RequestFailed
    deadline_expirations: int = 0

    @property
    def items_deduped(self) -> int:
        return self.items_requested - self.items_executed


@dataclasses.dataclass(frozen=True)
class _SweepInfo:
    """Grid geometry a sweep ticket needs to assemble its result."""
    grid_shape: tuple[int, ...]
    axis_names: tuple[str, ...]
    n_points: int
    slice_sizes: tuple[int, ...]   # points per canonical slice, in order
    slice_names: tuple[str, ...]


@dataclasses.dataclass
class _AdaptiveState:
    """Planner-side record of one base stream's importance-grid chain.

    ``chash``/``edges``/``epoch`` track the *current* (deepest) epoch
    stream; ``base_family`` is the canonical pre-grid family every
    pilot evaluates (pilots never sample through the grid being refit —
    :func:`repro.core.adaptive.pilot_weights` maps its own uniforms).
    ``frozen`` marks a converged chain (a refit reproduced the current
    edges); it is in-memory only, but a resumed engine re-derives it
    from the same deterministic pilot.
    """

    base_chash: str
    base_family: object     # the canonical pre-grid IntegrandFamily
    sampler: str
    epoch: int
    edges: np.ndarray
    chash: str
    family: object          # the current epoch's adapted IntegrandFamily
    frozen: bool = False


@dataclasses.dataclass
class _Pending:
    ticket: int
    request: IntegrationRequest | SweepRequest
    entries: list[CacheEntry]
    event: threading.Event
    result: IntegrationResult | RequestFailed | None = None
    new_rounds_scheduled: bool = False
    sweep: _SweepInfo | None = None
    deadline: Deadline | None = None
    trace: _TicketTrace | None = None


class IntegrationEngine:
    """Batching, caching, fault-tolerant integral server."""

    def __init__(self, *, seed: int = 0, round_samples: int = 65536,
                 use_kernel: bool = True, mesh=None, fn_axis: str = "model",
                 sample_axes: Sequence[str] | None = None,
                 chunk: int = 8192, max_pending: int = 256,
                 max_rounds_per_wave: int = 8,
                 max_items_per_wave: int | None = None,
                 pipeline_waves: bool = True, max_restarts: int = 2,
                 max_retained_results: int = 4096,
                 watchdog: StepWatchdog | None = None,
                 state_dir: str | None = None,
                 compact_on_start: bool = False,
                 store_fsync: bool = True,
                 sweep_slice_points: int = DEFAULT_SWEEP_SLICE,
                 obs: Observability | None = None,
                 retry_policy: RetryPolicy | None = None,
                 faults=None, lease_ttl: float | None = 30.0,
                 adapt_bins: int = adaptive.N_BINS,
                 adapt_pilot_samples: int = 4096,
                 adapt_max_epochs: int = 3,
                 adapt_rounds_per_epoch: int = 2):
        # telemetry first: every layer below receives the same bundle
        self._own_obs = obs is None
        self.obs = obs if obs is not None else Observability.disabled()
        self.seed = int(seed)
        self.key = rng_lib.fold_key(self.seed, 0)
        # the ONE retry policy (rule RES001): `max_restarts` is kept as
        # shorthand for its attempt budget; an explicit policy wins
        if retry_policy is None:
            retry_policy = RetryPolicy(max_attempts=int(max_restarts) + 1,
                                       seed=self.seed)
        self.retry = retry_policy
        self.faults = (NULL_FAULTS if faults is None
                       else faults).bind(self.obs)
        self.store = None
        if state_dir is not None:
            self.store = DurableStore(state_dir, fsync=store_fsync,
                                      obs=self.obs, faults=self.faults,
                                      lease_ttl=lease_ttl)
        self.cache = ResultCache(round_samples=round_samples,
                                 store=self.store, obs=self.obs)
        if sample_axes is None and mesh is not None:
            sample_axes = tuple(a for a in mesh.axis_names if a != fn_axis)
        if mesh is not None:
            sample_par = int(np.prod([mesh.shape[a] for a in sample_axes]))
            # the unfused fallback (sharded_family_sums) rounds the budget
            # up to per-shard multiples; an inexact split would draw
            # overlapping counters across consecutive cache rounds
            if round_samples % sample_par:
                raise ValueError(
                    f"round_samples={round_samples} must divide evenly over "
                    f"the {sample_par} sample-axis shards of the mesh")
        self.batcher = RoundBatcher(
            self.cache, self.key, use_kernel=use_kernel, mesh=mesh,
            fn_axis=fn_axis, sample_axes=sample_axes or ("data",),
            chunk=chunk, obs=self.obs, faults=self.faults)
        if self.store is not None:
            # only after every constructor check passed: a rejected
            # configuration must not pin meta into a fresh state dir.
            # A state dir replays one counter stream — same seed, same
            # round quantization, or the resumed samples would differ.
            self.store.ensure_meta({"seed": self.seed,
                                    "round_samples": int(round_samples)})
            if compact_on_start:
                self.cache.snapshot_to_store()
        if int(sweep_slice_points) < 1:
            raise ValueError("sweep_slice_points must be >= 1")
        # part of the dedupe contract: engines chunking at different
        # quanta never share sweep streams (see canonical.sweep_slices)
        self.sweep_slice_points = int(sweep_slice_points)
        self.max_pending = int(max_pending)
        self.max_rounds_per_wave = int(max_rounds_per_wave)
        if max_items_per_wave is not None and int(max_items_per_wave) <= 0:
            # 0 would silently mean "unbounded" in the planner's
            # truthiness check — reject it loudly instead
            raise ValueError("max_items_per_wave must be positive "
                             "(or None for unbounded)")
        self.max_items_per_wave = (None if max_items_per_wave is None
                                   else int(max_items_per_wave))
        self.pipeline_waves = bool(pipeline_waves)
        self.max_restarts = self.retry.max_attempts - 1
        self.max_retained_results = int(max_retained_results)
        self.watchdog = watchdog if watchdog is not None else StepWatchdog()
        # importance-grid adaptation knobs (docs/adaptive.md): pilots
        # and refit cadence are deterministic in (seed, base stream,
        # epoch) + durable rounds_done, so two engines with the same
        # knobs replay the same epoch chain
        if int(adapt_bins) < 2:
            raise ValueError("adapt_bins must be >= 2")
        if int(adapt_max_epochs) < 1 or int(adapt_rounds_per_epoch) < 1:
            raise ValueError("adapt_max_epochs and adapt_rounds_per_epoch "
                             "must be >= 1")
        self.adapt_bins = int(adapt_bins)
        self.adapt_pilot_samples = int(adapt_pilot_samples)
        self.adapt_max_epochs = int(adapt_max_epochs)
        self.adapt_rounds_per_epoch = int(adapt_rounds_per_epoch)
        self._adaptive: dict[str, _AdaptiveState] = {}
        self.stats = EngineStats()

        self._pending: dict[int, _Pending] = {}
        # FIFO-bounded: a continuously-serving engine must not retain
        # every result ever served; clients that care call release()
        self._results: collections.OrderedDict[int, IntegrationResult] = \
            collections.OrderedDict()
        self._next_ticket = 0
        # rounds dispatched but not yet deposited, per stream: the
        # planner schedules *beyond* these (pipelined waves, racing
        # step() drivers) instead of re-planning them
        self._inflight: dict[str, int] = {}
        self._rr_cursor = 0
        self._wave_seq = 0
        self._lock = threading.RLock()
        self._work_cv = threading.Condition(self._lock)
        self._space_cv = threading.Condition(self._lock)
        self._deposit_cv = threading.Condition(self._lock)
        self._worker: threading.Thread | None = None
        self._stop = False
        # armed by the first completed stop(): makes stop()/close()
        # re-entrant (second call is a no-op, no double snapshot)
        self._shutdown = False

    # -- submit / poll --------------------------------------------------------
    @property
    def running(self) -> bool:
        return self._worker is not None and self._worker.is_alive()

    def submit(self, request: IntegrationRequest | SweepRequest, *,
               block: bool = True, timeout: float | None = None) -> int:
        """Register a request; returns a ticket for :meth:`poll`/:meth:`result`.

        Accepts both request shapes — a :class:`SweepRequest` dispatches
        to :meth:`submit_sweep`.  Pure cache hits complete inline (no
        waiting, no launches, and no pending-table space needed).
        Otherwise, when the pending table is full, blocks until space
        frees up — or raises :class:`Backpressure` with ``block=False``.
        A rejected submit allocates nothing: counter-space ranges are
        only consumed once the request is accepted.
        """
        if isinstance(request, SweepRequest):
            return self.submit_sweep(request, block=block, timeout=timeout)
        # adaptation needs a precision target to chase (a pure sample
        # budget has nothing to adapt toward — the flag is ignored) and
        # never applies to swept slices (the sweep table and the grid
        # map would compete for the packed row; see docs/adaptive.md)
        adapt = bool(getattr(request, "adaptive", False)
                     and request.target_stderr is not None)
        with self.obs.span("submit", n_fn=sum(f.n_fn for f in
                                              request.families)) as span:
            canon_fams = []
            for fam in request.families:
                canon = canonical_family(fam)
                chash = (f"{family_hash(canon, canonicalize=False)}:"
                         f"{request.sampler}")
                if adapt and not canon.swept:
                    with _Acquire(self._lock, span.part("lock_wait")):
                        ast = self._adaptive_state(chash, canon,
                                                   request.sampler, span)
                    canon_fams.append((ast.chash, ast.family))
                else:
                    canon_fams.append((chash, canon))
            return self._submit_canonical(request, canon_fams, block=block,
                                          timeout=timeout, span=span)

    def submit_sweep(self, request: SweepRequest, *, block: bool = True,
                     timeout: float | None = None) -> int:
        """Register a parameter sweep; returns a ticket like :meth:`submit`.

        The grid canonicalizes into fixed ``sweep_slice_points``-sized
        slices of swept families (``canonical.sweep_slices``) — each
        slice one cache stream, so counter-space placement, top-up,
        persistence and the STR001–006 invariants apply per slice
        unchanged, and an overlapping sweep from another client dedupes
        onto the shared slices.  When the template names a registered
        kernel form, the (dim, sampler, compactified, sweep) capability
        is checked here, eagerly, with ``registry.lookup(...,
        required=True)`` — a sweep the fused path cannot serve fails at
        submit with the nearest supported combo named, instead of
        silently falling back for 10^5 points.
        """
        with self.obs.span("submit") as span:
            return self._submit_sweep(request, block=block, timeout=timeout,
                                      span=span)

    def _submit_sweep(self, request: SweepRequest, *, block: bool,
                      timeout: float | None, span) -> int:
        with self.obs.span("sweep_plan", template=request.template.name,
                           axes=len(request.grid)):
            fams, shape, axis_names = sweep_slices(
                request.template, request.grid,
                slice_points=self.sweep_slice_points)
            probe = fams[0]
            if probe.kernel is not None:
                from repro.kernels import registry
                if registry.form(probe.kernel) is not None:
                    registry.lookup(probe.kernel, dim=probe.dim,
                                    sampler=request.sampler,
                                    compactified=probe.compact,
                                    sweep=probe.swept,
                                    adapted=bool(probe.adapt_bins),
                                    required=True)
            canon_fams = [
                (f"{family_hash(f, canonicalize=False)}:{request.sampler}", f)
                for f in fams]
        n_points = int(np.prod(shape))
        span.set(n_fn=n_points)
        shared = sum(1 for chash, f in canon_fams
                     if self.cache.get(chash, f) is not None)
        self.obs.m["sweep_submitted"].inc()
        self.obs.m["sweep_points"].inc(n_points)
        if shared:
            self.obs.m["sweep_slices"].inc(shared, outcome="shared")
        if len(canon_fams) - shared:
            self.obs.m["sweep_slices"].inc(len(canon_fams) - shared,
                                           outcome="new")
        sweep = _SweepInfo(grid_shape=shape, axis_names=axis_names,
                           n_points=n_points,
                           slice_sizes=tuple(f.n_fn for f in fams),
                           slice_names=tuple(f.name for f in fams))
        return self._submit_canonical(request, canon_fams, block=block,
                                      timeout=timeout, sweep=sweep,
                                      span=span)

    def _submit_canonical(self, request, canon_fams, *, block: bool,
                          timeout: float | None, span,
                          sweep: _SweepInfo | None = None) -> int:
        """Shared tail of :meth:`submit`/:meth:`submit_sweep`: cache-hit
        peek, pending-table admission, allocation.  ``span`` is the
        ``submit`` span: its ``lock_wait`` parts time the engine lock."""
        # hit path needs no allocation: all entries must already exist
        # (a persisted stream from a previous process counts — passing
        # the family lets the cache rehydrate it, so a warm *restart*
        # serves satisfied requests with zero launches too)
        peek = [self.cache.get(chash, canon) for chash, canon in canon_fams]
        if all(e is not None for e in peek):
            req = request
            if all(self.cache.meets(e, target_stderr=req.target_stderr,
                                    n_samples=req.n_samples) for e in peek):
                with _Acquire(self._lock, span.part("lock_wait")):
                    ticket = self._new_ticket()
                    pend = _Pending(ticket=ticket, request=request,
                                    entries=list(peek),
                                    event=threading.Event(), sweep=sweep,
                                    trace=self._ticket_trace(span))
                    span.set(ticket=ticket, cache="hit")
                    self.stats.cache_hits += 1
                    self.obs.m["cache_requests"].inc(outcome="hit")
                    self._finish(pend, served_from_cache=True)
                return ticket

        with _Acquire(self._lock, span.part("lock_wait")):
            while len(self._pending) >= self.max_pending:
                if not block:
                    raise Backpressure(
                        f"{len(self._pending)} requests pending "
                        f"(max_pending={self.max_pending})")
                if not self._space_cv.wait(timeout=timeout):
                    raise Backpressure("timed out waiting for pending space")
            entries = [self.cache.get_or_allocate(chash, canon)
                       for chash, canon in canon_fams]
            ticket = self._new_ticket()
            budget = getattr(request, "deadline", None)
            pend = _Pending(ticket=ticket, request=request, entries=entries,
                            event=threading.Event(), sweep=sweep,
                            deadline=(None if budget is None
                                      else Deadline(budget)),
                            trace=self._ticket_trace(span))
            if self._meets(pend):     # became satisfiable while we waited
                span.set(ticket=ticket, cache="hit")
                self.stats.cache_hits += 1
                self.obs.m["cache_requests"].inc(outcome="hit")
                self._finish(pend, served_from_cache=True)
                return ticket
            span.set(ticket=ticket, cache="miss")
            self.obs.m["cache_requests"].inc(outcome="miss")
            self._pending[ticket] = pend
            self.obs.m["pending"].set(len(self._pending))
            self._work_cv.notify_all()
        return ticket

    def _ticket_trace(self, span) -> _TicketTrace | None:
        """A ticket's trace record, admitted now (tracing on only)."""
        if not self.obs.tracing:
            return None
        return _TicketTrace(tid=current_tid(), submit_ns=span.t0,
                            admit_ns=_clock.monotonic_ns())

    def _new_ticket(self) -> int:
        ticket = self._next_ticket
        self._next_ticket += 1
        self.stats.submitted += 1
        self.obs.m["submitted"].inc()
        return ticket

    def poll(self, ticket: int) -> IntegrationResult | None:
        """Finished result for ``ticket``, or None while in flight.

        Results are retained FIFO up to ``max_retained_results``;
        long-lived clients should :meth:`release` tickets they are done
        with rather than rely on retention.
        """
        with self._lock:
            return self._results.get(ticket)

    def sweep_partial(self, ticket: int,
                      since: np.ndarray | None = None) -> SweepResult:
        """Per-point snapshot of a sweep, streamed as rounds complete.

        Non-blocking: for a finished sweep this is exactly the final
        :class:`SweepResult`; while in flight it carries the current
        estimate of every point whose slice has deposited at least one
        round (``points_done`` marks them; undone points hold NaN means
        and inf stderrs) with ``complete=False``.  Slices finish in
        counter order within a wave, so a client can consume a large
        sweep incrementally instead of blocking for the whole grid.

        ``since`` makes the poll *incremental*: pass the previous
        snapshot's ``points_done`` mask and only slices with points not
        yet covered by it are finalized — an already-reported slice is
        marked done but carries NaN/inf placeholders (the caller keeps
        its previous values).  A poll loop over a large grid then pays
        the per-point finalize cost once per point, not once per poll.
        The mask covers the full grid including any final partial slice
        of a grid that is not a multiple of the slice quantum.
        """
        with self._lock:
            res = self._results.get(ticket)
            if res is None:
                pend = self._pending.get(ticket)
                if pend is None:
                    raise KeyError(f"unknown ticket {ticket}")
                if pend.sweep is None:
                    raise TypeError(f"ticket {ticket} is not a sweep")
                sw = pend.sweep
                if since is not None:
                    since = np.asarray(since, bool)
                    if since.shape != (sw.n_points,):
                        raise ValueError(
                            f"since mask has shape {since.shape}; expected "
                            f"({sw.n_points},) — pass the previous "
                            f"snapshot's points_done unchanged")
                means, errs, done = [], [], []
                offset = 0
                for entry, size in zip(pend.entries, sw.slice_sizes):
                    # explicit per-slice extent: the final slice of a
                    # grid that is not a multiple of the slice quantum
                    # is shorter, and the mask must align point-exactly
                    seen = (since is not None
                            and bool(np.all(since[offset:offset + size])))
                    offset += size
                    if entry.rounds_done > 0:
                        done.append(np.ones(size, bool))
                        if seen:
                            means.append(np.full(size, np.nan, np.float32))
                            errs.append(np.full(size, np.inf, np.float32))
                        else:
                            snap = entry.finalize()
                            means.append(np.asarray(snap.mean))
                            errs.append(np.asarray(snap.stderr))
                    else:
                        means.append(np.full(size, np.nan, np.float32))
                        errs.append(np.full(size, np.inf, np.float32))
                        done.append(np.zeros(size, bool))
                return SweepResult(
                    means=np.concatenate(means),
                    stderrs=np.concatenate(errs),
                    n_per_family=tuple(e.n for e in pend.entries),
                    names=sw.slice_names, served_from_cache=False,
                    ticket=ticket,
                    stream_ids=tuple(e.chash for e in pend.entries),
                    grid_shape=sw.grid_shape, axis_names=sw.axis_names,
                    n_points=sw.n_points,
                    points_done=np.concatenate(done), complete=False)
        if not isinstance(res, SweepResult):
            raise TypeError(f"ticket {ticket} is not a sweep")
        return res

    def release(self, ticket: int) -> None:
        """Drop a finished result the client no longer needs."""
        with self._lock:
            self._results.pop(ticket, None)

    def result(self, ticket: int,
               timeout: float | None = None) -> IntegrationResult:
        """Block until ``ticket`` finishes (worker thread must be running
        or another thread driving :meth:`step`).

        A request that failed permanently (retry budget exhausted,
        deadline expired, stream quarantined) returns its structured
        :class:`~repro.service.api.RequestFailed` — a completed ticket,
        not a hang.
        """
        with self._lock:
            res = self._results.get(ticket)
            if res is not None:
                return res
            pend = self._pending.get(ticket)
        if pend is None:
            raise KeyError(f"unknown ticket {ticket}")
        if not pend.event.wait(timeout=timeout):
            with self._lock:
                state = ("pending" if ticket in self._pending
                         else "completing")
                rounds = [e.rounds_done for e in pend.entries]
            raise TimeoutError(
                f"ticket {ticket} still {state} after {timeout:g}s "
                f"(worker {'running' if self.running else 'NOT running'}, "
                f"rounds folded per stream: {rounds})")
        return pend.result

    # -- the wave loop --------------------------------------------------------
    def step(self) -> bool:
        """Run one batching wave synchronously.

        Returns True when work was executed (or is executing in another
        driver's wave), False when the pending table made no progress
        (empty or already satisfiable).
        """
        with _Acquire(self._lock, self.obs.span("lock_wait",
                                                wave=self._wave_seq)):
            seq = self._wave_seq
            items, riders = self._plan_traced(seq)
            if not items:
                with self.obs.span("complete", wave=seq):
                    self._complete_ready()
                if self._awaiting_other_driver_locked():
                    # every remaining round is in another driver's wave;
                    # wait for a deposit instead of claiming deadlock
                    with self.obs.span("idle", wave=seq):
                        self._deposit_cv.wait(timeout=1.0)
                    return True
                return False
            self._wave_seq += 1

        def wave(attempt: int) -> int:
            if attempt:
                with self._lock:
                    self.stats.restarts += 1
                self.obs.m["retries"].inc(stage="wave")
            self.faults.check("plan")
            with self.watchdog:
                return self.batcher.execute(items)

        t0 = _clock.monotonic()
        stragglers_before = self.watchdog.straggler_count
        self._stamp_launch(riders)
        try:
            with self.obs.wave(seq):
                executed = run_with_policy(
                    wave, self.retry, stage="wave", counter=seq,
                    deadline=self._wave_deadline(items, seq),
                    on_retry=self._restart_hook("wave_restart", seq, items))
        except (RetryExhausted, DeadlineExceeded) as exc:
            # the wave is permanently lost: complete its tickets with a
            # structured failure, then surface the error to this
            # synchronous driver (async drivers swallow and move on)
            with self._lock:
                self._retire_items(items)
                self._fail_wave(items, exc)
            raise
        except Exception:
            with self._lock:
                self._retire_items(items)
            raise
        self._note_stragglers(stragglers_before, seq, items)
        self.obs.m["waves"].inc()
        self.obs.m["wave_seconds"].observe(_clock.monotonic() - t0)
        self._complete_wave(items, executed, seq)
        return True

    def _complete_wave(self, items: Sequence[WorkItem], executed: int,
                       seq: int) -> None:
        """Retire a deposited wave and finish the requests it met."""
        with _Acquire(self._lock, self.obs.span("lock_wait", wave=seq)):
            with self.obs.span("complete", wave=seq):
                self._retire_items(items)
                self.stats.waves += 1
                self.stats.items_executed += executed
                self._complete_ready()

    def _plan_traced(self, seq: int) -> tuple[list[WorkItem], list[_Pending]]:
        """Plan wave ``seq`` inside its ``plan`` span (caller holds the
        lock; a refit's journal writes carry the wave too).  With
        tracing on, also returns the wave's riders, whose tickets the
        span carries and whose trace records count it."""
        with self.obs.wave(seq), self.obs.span(
                "plan", pending=len(self._pending)) as span:
            items = self._plan_wave(span)
            riders: list[_Pending] = []
            if items and self.obs.tracing:
                per_stream = collections.Counter(it.chash for it in items)
                for pend in self._pending.values():
                    rounds = sum(per_stream[e.chash] for e in pend.entries)
                    if rounds and pend.trace is not None:
                        pend.trace.waves += 1
                        pend.trace.rounds += rounds
                        riders.append(pend)
                span.set(tickets=[p.ticket for p in riders])
        return items, riders

    @staticmethod
    def _stamp_launch(riders: Sequence[_Pending]) -> None:
        """Note the first launch that carries each rider's rounds."""
        if riders:
            now = _clock.monotonic_ns()
            for pend in riders:
                if pend.trace.launch_ns is None:
                    pend.trace.launch_ns = now

    # -- telemetry hooks ------------------------------------------------------
    def _restart_hook(self, kind: str, seq: int,
                      items: Sequence[WorkItem]):
        """on_restart callback emitting a structured event carrying the
        wave sequence number and the affected stream identities."""
        def on_restart(attempt: int, exc: Exception) -> None:
            self.obs.m["restarts"].inc()
            self.obs.event(kind, wave=seq, attempt=attempt,
                           error=type(exc).__name__,
                           streams=_wave_streams(items))
        return on_restart

    def _note_stragglers(self, before: int, seq: int,
                         items: Sequence[WorkItem]) -> None:
        """Emit one instant event per watchdog straggler the wave added."""
        new = self.watchdog.straggler_count - before
        if new <= 0:
            return
        self.obs.m["stragglers"].inc(new)
        for ev in self.watchdog.events[-new:]:
            self.obs.event("straggler", wave=seq, step=ev.step,
                           duration=ev.duration, median=ev.median,
                           streams=_wave_streams(items))

    def stderr_trajectory(self, chash: str):
        """Per-stream convergence record: the stderr-vs-rounds trajectory
        observed at deposit time (requires convergence recording, i.e. an
        engine built with ``Observability.enabled()``).  ``chash`` is a
        stream id as reported by ``IntegrationResult.stream_ids``."""
        return self.obs.convergence.trajectory(chash)

    def _awaiting_other_driver_locked(self) -> bool:
        return any(self._inflight.get(e.chash) for p in self._pending.values()
                   for e in p.entries)

    # -- failure surfacing ----------------------------------------------------
    def _wave_deadline(self, items: Sequence[WorkItem],
                       seq: int) -> Deadline | None:
        """Tightest remaining per-request deadline riding wave ``seq``,
        as a fresh budget for the retry loop (None when no rider has
        one)."""
        streams = {it.chash for it in items}
        with _Acquire(self._lock, self.obs.span("lock_wait", wave=seq)):
            remains = [p.deadline.remaining()
                       for p in self._pending.values()
                       if p.deadline is not None
                       and any(e.chash in streams for e in p.entries)]
        if not remains:
            return None
        return Deadline(max(min(remains), 1e-9))

    def _fail_wave(self, items: Sequence[WorkItem], exc: Exception) -> None:
        """Complete the tickets a permanently-failed wave was serving
        with a structured :class:`RequestFailed` (caller holds the lock).

        A :class:`DeadlineExceeded` fails only the riders whose own
        deadline ran out — other requests on the same streams simply get
        rescheduled; :class:`RetryExhausted` fails every rider.
        """
        streams = {it.chash for it in items}
        riders = [p for p in self._pending.values()
                  if any(e.chash in streams for e in p.entries)]
        if isinstance(exc, DeadlineExceeded):
            riders = [p for p in riders
                      if p.deadline is not None and p.deadline.expired]
            reason = "deadline"
        else:
            reason = "retry_exhausted"
        for pend in riders:
            del self._pending[pend.ticket]
            if reason == "deadline":
                self.stats.deadline_expirations += 1
                self.obs.m["deadline_expirations"].inc()
            self._fail(pend, reason=reason,
                       stage=getattr(exc, "stage", None),
                       attempts=getattr(exc, "attempts", 0),
                       message=str(exc))
        if riders:
            self.obs.m["pending"].set(len(self._pending))
            self._space_cv.notify_all()

    def _fail(self, pend: _Pending, *, reason: str, stage: str | None = None,
              attempts: int = 0, message: str = "") -> None:
        """Terminal completion of one ticket as ``RequestFailed``
        (caller holds the lock)."""
        pend.result = RequestFailed(
            ticket=pend.ticket, reason=reason, stage=stage,
            attempts=attempts, message=message,
            stream_ids=tuple(e.chash for e in pend.entries))
        self._results[pend.ticket] = pend.result
        while len(self._results) > self.max_retained_results:
            self._results.popitem(last=False)
        self.stats.failed += 1
        self.obs.event("request_failed", ticket=pend.ticket, reason=reason,
                       stage=stage, streams=[c[:16]
                                             for c in pend.result.stream_ids])
        self._trace_request(pend, failed=reason)
        pend.event.set()

    def _trace_request(self, pend: _Pending, **args) -> None:
        """The ``request`` span of a finished ticket, submit to now, on
        the thread that submitted it (tracing on only)."""
        tr = pend.trace
        if tr is None:
            return
        queue_us = (None if tr.launch_ns is None
                    else (tr.launch_ns - tr.admit_ns) // 1000)
        self.obs.complete("request", tr.submit_ns, _clock.monotonic_ns(),
                          tr.tid, ticket=pend.ticket, queue_us=queue_us,
                          waves=tr.waves, rounds=tr.rounds, **args)

    # -- importance-grid adaptation -------------------------------------------
    def _pilot_key(self, base_chash: str, epoch: int) -> tuple:
        """Counter key of the (base stream, epoch) pilot wave.

        Folded onto a stream id derived from the base hash and the
        epoch being fit, so pilot counters can never collide with the
        engine's main sample streams (which fold on stream 0) and a
        resumed planner re-draws the identical pilot.
        """
        sid = zlib.crc32(f"adapt:{base_chash}:{int(epoch)}".encode())
        return rng_lib.fold_key(self.seed, sid)

    def _adaptive_state(self, base_chash: str, canon, sampler: str,
                        span) -> _AdaptiveState:
        """Active importance-grid state for one base stream (caller
        holds the lock); a fresh fit is the ``pilot`` part of ``span``.

        Resume first: when the WAL/snapshot carries an epoch chain
        rooted at ``base_chash`` the planner adopts its tip — recorded
        chash, recorded edges — so the resumed stream samples through
        exactly the journaled grid (refitting could differ only if the
        code changed; the record is the contract).  Otherwise epoch 1
        is fit here, at submit, from a deterministic pilot, and its
        grid is journaled *before* the child stream's alloc (STR007).
        """
        ast = self._adaptive.get(base_chash)
        if ast is not None:
            return ast
        tip = self.cache.grid_tip(base_chash)
        if tip is not None:
            fam = canon.adapted(tip.edges, epoch=tip.epoch)
            ast = _AdaptiveState(
                base_chash=base_chash, base_family=canon, sampler=sampler,
                epoch=tip.epoch, edges=np.asarray(tip.edges),
                chash=tip.chash, family=fam)
        else:
            with span.part("pilot"):
                edges = adaptive.initial_edges(np.asarray(canon.domains),
                                               self.adapt_bins)
                weights = adaptive.pilot_weights(
                    canon, edges, self._pilot_key(base_chash, 1),
                    self.adapt_pilot_samples)
                edges = adaptive.refine_edges(edges, weights)
            self.obs.m["grid_pilots"].inc()
            fam = canon.adapted(edges, epoch=1)
            chash = f"{family_hash(fam, canonicalize=False)}:{sampler}"
            self.cache.register_grid(chash, parent=base_chash, epoch=1,
                                     edges=edges)
            self.obs.m["adapted_streams"].inc()
            ast = _AdaptiveState(
                base_chash=base_chash, base_family=canon, sampler=sampler,
                epoch=1, edges=edges, chash=chash, family=fam)
        self._adaptive[base_chash] = ast
        return ast

    def _maybe_refit_locked(self, span) -> None:
        """Open the next grid epoch for adapted streams still chasing
        their stderr target (caller holds the lock); each pilot and
        refinement is a ``refit`` part of the ``plan`` span ``span``.

        Every trigger input is durable or deterministic — the current
        epoch stream's ``rounds_done`` (WAL-recovered), the rider's
        target, and a pilot counter-keyed by (seed, base stream,
        epoch) — so a SIGKILLed engine re-decides the identical chain.
        Refits only fire at a wave boundary with nothing in flight on
        the stream; the new epoch is a NEW cache stream (grid
        journaled first — STR007) and every pending holding the old
        entry is swapped to the child, so results finalize from the
        last epoch only.  A refit that reproduces the current edges
        freezes the chain: the grid converged.
        """
        for ast in self._adaptive.values():
            if ast.frozen or ast.epoch >= self.adapt_max_epochs:
                continue
            if self._inflight.get(ast.chash):
                continue
            entry = self.cache.get(ast.chash)
            if entry is None or entry.quarantined:
                continue
            if entry.rounds_done < self.adapt_rounds_per_epoch:
                continue
            targets = [p.request.target_stderr
                       for p in self._pending.values()
                       if p.request.target_stderr is not None
                       and any(e.chash == ast.chash for e in p.entries)]
            if not targets:
                continue    # no rider is still chasing precision
            if self.cache.meets(entry, target_stderr=min(targets),
                                n_samples=None):
                continue    # met — _complete_ready finishes the riders
            epoch = ast.epoch + 1
            with span.part("refit"):
                weights = adaptive.pilot_weights(
                    ast.base_family, ast.edges,
                    self._pilot_key(ast.base_chash, epoch),
                    self.adapt_pilot_samples)
                edges = adaptive.refine_edges(ast.edges, weights)
            self.obs.m["grid_pilots"].inc()
            if np.array_equal(edges, ast.edges):
                ast.frozen = True    # a resume re-derives this verdict
                continue
            fam = ast.base_family.adapted(edges, epoch=epoch)
            chash = f"{family_hash(fam, canonicalize=False)}:{ast.sampler}"
            self.cache.register_grid(chash, parent=ast.chash, epoch=epoch,
                                     edges=edges)
            child = self.cache.get_or_allocate(chash, fam)
            for pend in self._pending.values():
                pend.entries = [child if e.chash == ast.chash else e
                                for e in pend.entries]
            self.obs.m["adapted_streams"].inc()
            self.obs.m["grid_refits"].inc()
            self.obs.event("grid_refit", base=ast.base_chash[:16],
                           parent=ast.chash[:16], stream=chash[:16],
                           epoch=epoch)
            ast.chash, ast.edges, ast.epoch, ast.family = \
                chash, edges, epoch, fam

    def _plan_wave(self, span) -> list[WorkItem]:
        """Assign the wave's round budget fairly across pending requests.

        Needs are computed beyond each stream's fold frontier plus rounds
        already in flight (a pipelined or racing wave).  Allocation is
        round-robin — one round per stream per pass, the starting stream
        rotating every wave — so with a bounded ``max_items_per_wave``
        every pending request makes progress every wave: heavy precision
        asks cannot monopolize the budget.  Scheduled rounds are
        registered in-flight; callers retire them after deposit (or on
        permanent failure).  Caller must hold the engine lock; ``span``
        is the ``plan`` span.
        """
        if self._adaptive:
            self._maybe_refit_locked(span)
        info: dict[str, dict] = {}
        order: list[str] = []
        for pend in self._pending.values():
            if pend.deadline is not None and pend.deadline.expired:
                continue     # _complete_ready fails it; no more rounds
            req = pend.request
            for entry in pend.entries:
                if entry.quarantined:
                    continue  # poison ladder: stream is unschedulable
                inflight = self._inflight.get(entry.chash, 0)
                raw = self.cache.rounds_needed(
                    entry, target_stderr=req.target_stderr,
                    n_samples=req.n_samples, max_rounds=1 << 16)
                need = min(max(0, raw - inflight), self.max_rounds_per_wave)
                if need or inflight:
                    # rounds are being computed on this request's behalf
                    pend.new_rounds_scheduled = True
                self.stats.items_requested += need
                rec = info.get(entry.chash)
                if rec is None:
                    info[entry.chash] = {"entry": entry,
                                         "sampler": req.sampler,
                                         "need": need}
                    order.append(entry.chash)
                else:
                    rec["need"] = max(rec["need"], need)
        if not any(info[c]["need"] for c in order):
            return []

        budget = (self.max_items_per_wave if self.max_items_per_wave
                  else (1 << 62))
        alloc = dict.fromkeys(order, 0)
        start = self._rr_cursor % len(order)
        self._rr_cursor += 1
        progress = True
        while budget > 0 and progress:
            progress = False
            for k in range(len(order)):
                chash = order[(start + k) % len(order)]
                if alloc[chash] < info[chash]["need"] and budget > 0:
                    alloc[chash] += 1
                    budget -= 1
                    progress = True

        items: list[WorkItem] = []
        for chash in order:
            if not alloc[chash]:
                continue
            rec = info[chash]
            frontier = (rec["entry"].rounds_done
                        + self._inflight.get(chash, 0))
            items.extend(
                WorkItem(chash=chash, round_index=r, sampler=rec["sampler"])
                for r in range(frontier, frontier + alloc[chash]))
            self._inflight[chash] = (self._inflight.get(chash, 0)
                                     + alloc[chash])
        self.obs.m["inflight"].set(sum(self._inflight.values()))
        return items

    def _retire_items(self, items: Sequence[WorkItem]) -> None:
        """Drop items from the in-flight table (deposited or abandoned).
        Caller must hold the engine lock."""
        for it in items:
            left = self._inflight.get(it.chash, 0) - 1
            if _analysis.asserts_enabled():
                # a negative in-flight count means a wave was retired
                # twice — the precursor of double-scheduling its rounds
                _analysis.assert_inflight_consistent(it.chash[:16], left)
            if left > 0:
                self._inflight[it.chash] = left
            else:
                self._inflight.pop(it.chash, None)
        self.obs.m["inflight"].set(sum(self._inflight.values()))
        self._deposit_cv.notify_all()

    def _meets(self, pend: _Pending) -> bool:
        req = pend.request
        return all(
            self.cache.meets(e, target_stderr=req.target_stderr,
                             n_samples=req.n_samples)
            for e in pend.entries)

    def _complete_ready(self) -> None:
        done = [p for p in self._pending.values() if self._meets(p)]
        for pend in done:
            del self._pending[pend.ticket]
            self._finish(pend,
                         served_from_cache=not pend.new_rounds_scheduled)
        # graceful degradation, terminal branch: a pending that can
        # never be met — its stream quarantined, or its deadline gone —
        # completes as RequestFailed instead of parking forever
        failed = []
        for pend in self._pending.values():
            bad = [e.chash[:16] for e in pend.entries if e.quarantined]
            if bad:
                failed.append((pend, "quarantined",
                               f"stream(s) {', '.join(bad)} quarantined "
                               f"after repeated non-finite deposits"))
            elif pend.deadline is not None and pend.deadline.expired:
                failed.append((pend, "deadline",
                               f"deadline budget {pend.deadline.budget:g}s "
                               f"expired"))
        for pend, reason, message in failed:
            del self._pending[pend.ticket]
            if reason == "deadline":
                self.stats.deadline_expirations += 1
                self.obs.m["deadline_expirations"].inc()
            self._fail(pend, reason=reason, message=message)
        if done or failed:
            self.obs.m["pending"].set(len(self._pending))
            self._space_cv.notify_all()

    def _finish(self, pend: _Pending, *, served_from_cache: bool) -> None:
        means, errs = [], []
        for entry in pend.entries:
            res = entry.finalize()
            means.append(np.asarray(res.mean))
            errs.append(np.asarray(res.stderr))
        if pend.sweep is not None:
            sw = pend.sweep
            pend.result = SweepResult(
                means=np.concatenate(means), stderrs=np.concatenate(errs),
                n_per_family=tuple(e.n for e in pend.entries),
                names=sw.slice_names,
                served_from_cache=served_from_cache, ticket=pend.ticket,
                stream_ids=tuple(e.chash for e in pend.entries),
                grid_shape=sw.grid_shape, axis_names=sw.axis_names,
                n_points=sw.n_points,
                points_done=np.ones(sw.n_points, bool), complete=True)
        else:
            pend.result = IntegrationResult(
                means=np.concatenate(means), stderrs=np.concatenate(errs),
                n_per_family=tuple(e.n for e in pend.entries),
                names=tuple(f.name for f in pend.request.families),
                served_from_cache=served_from_cache, ticket=pend.ticket,
                stream_ids=tuple(e.chash for e in pend.entries))
        self._results[pend.ticket] = pend.result
        while len(self._results) > self.max_retained_results:
            self._results.popitem(last=False)
        self.stats.served += 1
        self.obs.m["served"].inc()
        if served_from_cache:
            self.obs.m["warm_zero_launch"].inc()
        self._trace_request(pend)
        pend.event.set()

    # -- background worker ----------------------------------------------------
    def start(self) -> None:
        """Spawn the worker thread (idempotent)."""
        with self._lock:
            if self.running:
                return
            self._stop = False
            self._shutdown = False
            self._worker = threading.Thread(
                target=self._run, name="integration-engine", daemon=True)
            self._worker.start()

    def stop(self, timeout: float | None = 30.0) -> None:
        """Stop the worker and snapshot (re-entrant: a second stop()
        after a completed one is a no-op — no double snapshot)."""
        with self._lock:
            if self._shutdown and self._worker is None:
                return
            self._stop = True
            self._work_cv.notify_all()
            worker = self._worker
        if worker is not None:
            worker.join(timeout=timeout)
            if worker.is_alive():
                # mid-wave; keep the handle so running stays True and a
                # start() cannot spawn a second concurrent worker
                raise TimeoutError(
                    "worker still executing a wave; it will exit at the "
                    "wave boundary (retry stop())")
            self._worker = None
        # snapshot-on-shutdown: compact the journal once no worker can
        # deposit anymore (a kill before this point only costs replay)
        with self._lock:
            if self._shutdown:
                return
            self._shutdown = True
        self.checkpoint()

    def checkpoint(self) -> None:
        """Compact accumulated state into an atomic snapshot (no-op
        without a ``state_dir``).  Safe at any wave boundary."""
        if self.store is not None:
            self.cache.snapshot_to_store()

    def close(self, timeout: float | None = 30.0) -> None:
        """Clean shutdown: stop the worker, snapshot, release the store.

        If the worker outlives ``timeout`` the TimeoutError from
        :meth:`stop` still propagates, but the store handle is released
        regardless — the journal already holds every folded round, so
        skipping the shutdown snapshot costs replay time, never data.
        """
        try:
            self.stop(timeout=timeout)
        finally:
            if self.store is not None:
                self.store.close()
            if self._own_obs:
                self.obs.close()

    def __enter__(self) -> "IntegrationEngine":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    def drain(self, timeout: float | None = None) -> None:
        """Block until the pending table is empty (worker running)."""
        events = []
        with self._lock:
            events = [p.event for p in self._pending.values()]
        for ev in events:
            if not ev.wait(timeout=timeout):
                raise TimeoutError("pending requests did not drain")

    def _run(self) -> None:
        try:
            if self.pipeline_waves:
                self._run_pipelined()
                return
            while True:
                if self.store is not None:
                    self.store.heartbeat()   # idle engines keep the lease
                self.faults.check("worker_crash")
                with self._lock:
                    if not self._pending and not self._stop:
                        with self.obs.span("idle", wave=self._wave_seq):
                            while not self._pending and not self._stop:
                                self._work_cv.wait(timeout=0.5)
                    if self._stop:
                        return
                try:
                    self.step()
                except (RetryExhausted, DeadlineExceeded):
                    # step() already completed the affected tickets as
                    # RequestFailed; the worker keeps serving the rest
                    continue
        except InjectedCrash as exc:
            # chaos: the worker dies at a wave boundary like a real
            # thread crash would — durable state is intact, a driver
            # can resume via step() or a fresh start()
            self.obs.event("worker_crash", error=str(exc))

    def _run_pipelined(self) -> None:
        """Double-buffered wave loop: dispatch wave k+1, then deposit
        wave k.

        ``launch`` only enqueues device work (JAX async dispatch), so by
        the time ``deposit`` blocks on wave k's transfer the device is
        already chewing on wave k+1 — host-side folding, group-commit
        journaling and request completion all run off the device
        critical path.  Deposits stay in wave order, so the cache's
        in-order fold and the WAL's crash window are exactly those of
        the serial loop.  On ``stop()`` the tail wave is deposited
        before the worker exits.
        """
        inflight: tuple[InFlightWave, list[WorkItem], float, int] | None = \
            None
        while True:
            if self.store is not None:
                self.store.heartbeat()       # idle engines keep the lease
            if inflight is None:
                # wave boundary with nothing salvageable in flight: the
                # only spot where an injected worker death is loss-free
                self.faults.check("worker_crash")
            with _Acquire(self._lock, self.obs.span("lock_wait",
                                                    wave=self._wave_seq)):
                seq = self._wave_seq
                if not self._pending and inflight is None and not self._stop:
                    with self.obs.span("idle", wave=seq):
                        while (not self._pending and inflight is None
                               and not self._stop):
                            self._work_cv.wait(timeout=0.5)
                if self._stop and inflight is None:
                    return
                items, riders = (([], []) if self._stop
                                 else self._plan_traced(seq))
                if not items and inflight is None:
                    with self.obs.span("complete", wave=seq):
                        self._complete_ready()
                    if self._pending:
                        # nothing plannable here, rounds owed to another
                        # driver's wave: wait for its deposit
                        with self.obs.span("idle", wave=seq):
                            self._deposit_cv.wait(timeout=0.5)
                    continue
                if items:
                    self._wave_seq += 1

            handle = None
            t0 = _clock.monotonic()
            if items:
                def launch(attempt: int, _items=items) -> InFlightWave:
                    if attempt:
                        with self._lock:
                            self.stats.restarts += 1
                        self.obs.m["retries"].inc(stage="launch")
                    self.faults.check("plan")
                    with self.watchdog:
                        return self.batcher.launch(_items)

                stragglers_before = self.watchdog.straggler_count
                self._stamp_launch(riders)
                try:
                    with self.obs.wave(seq):
                        handle = run_with_policy(
                            launch, self.retry, stage="launch", counter=seq,
                            deadline=self._wave_deadline(items, seq),
                            on_retry=self._restart_hook(
                                "wave_restart", seq, items))
                except (RetryExhausted, DeadlineExceeded) as exc:
                    # permanent: complete the riders as RequestFailed
                    # and keep serving — the sibling wave deposits below
                    with self._lock:
                        self._retire_items(items)
                        self._fail_wave(items, exc)
                    handle = None
                except Exception:
                    # the worker is about to die: salvage the sibling
                    # wave first (its rounds are real), and make sure no
                    # in-flight registration outlives this thread — a
                    # leaked count would wedge every other driver's
                    # planner forever
                    with self._lock:
                        self._retire_items(items)
                    if inflight is not None:
                        try:
                            self._deposit_wave(*inflight)
                        except Exception:
                            pass   # _deposit_wave retired its items
                    raise
                self._note_stragglers(stragglers_before, seq, items)

            if inflight is not None:
                try:
                    self._deposit_wave(*inflight)
                except Exception:
                    if handle is not None:
                        with self._lock:
                            self._retire_items(items)
                    raise
            inflight = ((handle, items, t0, seq) if handle is not None
                        else None)

    def _deposit_wave(self, wave: InFlightWave, items: list[WorkItem],
                      t_launch: float | None = None, seq: int = 0) -> None:
        """Host side of one pipelined wave: transfer, group-commit, and
        complete ready requests.  A transient failure relaunches the
        wave (counter addressing makes the recomputation bit-identical;
        already-folded rounds are skipped on deposit)."""
        state = {"wave": wave}

        def attempt(k: int) -> int:
            if k:
                with self._lock:
                    self.stats.restarts += 1
                self.obs.m["retries"].inc(stage="deposit")
                state["wave"] = self.batcher.launch(items)
            with self.watchdog:
                return self.batcher.deposit(state["wave"])

        stragglers_before = self.watchdog.straggler_count
        try:
            with self.obs.wave(seq):
                executed = run_with_policy(
                    attempt, self.retry, stage="deposit", counter=seq,
                    deadline=self._wave_deadline(items, seq),
                    on_retry=self._restart_hook("deposit_retry", seq,
                                                items))
        except (RetryExhausted, DeadlineExceeded) as exc:
            # permanent loss of this wave only: fail its riders and let
            # the worker keep serving everything else
            with self._lock:
                self._retire_items(items)
                self._fail_wave(items, exc)
            return
        except Exception:
            with self._lock:
                self._retire_items(items)
            raise
        self._note_stragglers(stragglers_before, seq, items)
        self.obs.m["waves"].inc()
        if t_launch is not None:
            self.obs.m["wave_seconds"].observe(
                _clock.monotonic() - t_launch)
        self._complete_wave(items, executed, seq)
