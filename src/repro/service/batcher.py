"""Cross-request coalescing into fused multi-round dimension buckets.

The unit of work in the service is a **(canonical family, round)** pair:
``round_samples`` samples of one cached stream, addressed purely by
counters (key, fn_offset, round * round_samples).  This module takes the
set of work items one engine wave produced — typically spanning many
client requests at different cache fill levels — and evaluates them in
as few kernel launches as possible:

* per (stream, sampler) the wave's rounds form one contiguous **span**
  ``[start, start + count)`` rooted at the stream's fold frontier;
* spans are grouped by ``(sampler, count)`` and each group's families go
  to the fused multi-round planner (:mod:`repro.kernels.mc_eval.multi`),
  which buckets them by integrand dimension and evaluates ALL ``count``
  rounds of a bucket in ONE ``pallas_call`` (``eval_plan_rounds`` /
  ``sharded_eval_plan_rounds``) — an R-round refinement wave over B
  buckets costs B launches, not R x B.  Spans may start at different
  stream depths (a cold stream and a top-up fuse into the same launch:
  per-function-block ``round_base`` offsets carry each stream's window);
* families whose form is not fusable fall back to the chunked JAX path,
  one round at a time (still counter-addressed, still cacheable).

Evaluation is split into :meth:`RoundBatcher.launch` (device dispatch —
returns an :class:`InFlightWave` whose sums are still device futures
under JAX async dispatch) and :meth:`RoundBatcher.deposit` (host
transfer + one group-committed cache fold per wave).  A fused bucket's
round stack crosses to the host whole, with one copy, and is cut per
(stream, round) there: ``launch`` issues nothing after the kernel, so it
returns without waiting for the device.  The engine
pipelines the two: wave k+1's launch overlaps wave k's transfer and
deposit, keeping journaling off the device critical path.
:meth:`RoundBatcher.execute` composes them for synchronous drivers.

Deposits stay **side-effect free until the end of the wave** and are
folded in round order per entry.  Rounds the cache already folded are
skipped (a replayed or racing wave recomputes bit-identical sums), so a
crash-and-restart of a wave (``run_with_restarts``) and concurrent
``step()`` drivers are both safe.

Fusion plans (the packed/concatenated bucket operands) are cached per
(entry set, sampler) with **LRU eviction** — steady-state request mixes
keep their plans hot instead of periodically re-planning everything.
Adapted streams need no special handling here: every importance-grid
epoch is a distinct cache entry (its edges live in the family params and
therefore in the content hash), so an epoch swap changes the entry set
and naturally misses to a fresh plan while the old epoch's plan ages out
of the LRU.
Compiled kernels are reused more broadly still: bucket kernel names
encode only the shape signature, so a *new* entry set whose buckets
match previously-seen shapes reuses the compiled executable (see
:mod:`repro.kernels.mc_eval.multi`).
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Sequence

import jax
import numpy as np

from repro.analysis import streams as _analysis
from repro.core import direct_mc
from repro.core.direct_mc import SumsState
from repro.core.integrand import MultiFunctionSpec
from repro.service.cache import CacheEntry, ResultCache
from repro.service.faults import NULL_FAULTS


@dataclasses.dataclass(frozen=True)
class WorkItem:
    """One round of one cached stream."""
    chash: str
    round_index: int
    sampler: str


@dataclasses.dataclass(frozen=True)
class _Span:
    """One stream's contiguous slice of a wave: rounds [start, start+count)."""
    entry: CacheEntry
    sampler: str
    start: int
    count: int


class _FusedStack:
    """One fused bucket's ``(n_rounds, rows, 2)`` sums: a device future
    until first read, then ONE device-to-host copy, split on the host
    (:func:`repro.kernels.mc_eval.multi.split_rounds`)."""

    def __init__(self, sums, slices, round_samples: int, copies):
        self.device = sums
        self._slices = slices
        self._round_samples = round_samples
        self._copies = copies
        self._rounds = None

    def rounds(self) -> dict[int, tuple[SumsState, ...]]:
        if self._rounds is None:
            from repro.kernels.mc_eval import multi
            host = np.asarray(self.device)
            self._copies.inc()
            self._rounds = multi.split_rounds([(host, self._slices)],
                                              self._round_samples)
        return self._rounds


class _HostView:
    """``s1`` or ``s2`` of one (stream, round) in a fused bucket;
    ``np.asarray`` reads it through the bucket's single host copy."""

    __slots__ = ("stack", "family_index", "round", "field")

    def __init__(self, stack: _FusedStack, family_index: int, round: int,
                 field: str):
        self.stack = stack
        self.family_index = family_index
        self.round = round
        self.field = field

    def __array__(self, dtype=None, copy=None):
        rounds = self.stack.rounds()[self.family_index]
        return np.array(getattr(rounds[self.round], self.field),
                        dtype=dtype, copy=copy)


@dataclasses.dataclass
class InFlightWave:
    """A dispatched wave whose sums may still be computing on device.

    ``results`` holds ``(entry, round_index, sums)`` with each entry's
    rounds ascending.  A fused stream's ``sums`` are :class:`_HostView`
    fields over its bucket's stack and a host ``np.float32`` ``n``; a
    chunked-fallback stream's are jax values.  Either reads with
    ``np.asarray``; :meth:`RoundBatcher.deposit` blocks on the device and
    transfers whatever ``results`` holds when it runs.
    """
    results: list[tuple[CacheEntry, int, SumsState]]
    n_items: int


class RoundBatcher:
    """Coalesces work items into fused multi-round launches, one RNG key."""

    def __init__(self, cache: ResultCache, key, *, use_kernel: bool = True,
                 mesh=None, fn_axis: str = "model",
                 sample_axes: Sequence[str] = ("data",), chunk: int = 8192,
                 plan_cache_size: int = 256, obs=None, faults=None):
        if obs is None:
            from repro.obs import Observability
            obs = Observability.disabled()
        self.obs = obs
        self.faults = NULL_FAULTS if faults is None else faults
        self.cache = cache
        self.key = key
        self.use_kernel = bool(use_kernel)
        self.mesh = mesh
        self.fn_axis = fn_axis
        self.sample_axes = tuple(sample_axes)
        self.chunk = int(chunk)
        self.plan_cache_size = int(plan_cache_size)
        # rounds served by the chunked per-round path instead of a fused
        # launch; benchmarks/service_bench.py gates this at 0 for
        # registered-form workloads (compactified families included)
        self.fallback_rounds = 0
        self._plans: collections.OrderedDict[tuple, object] = \
            collections.OrderedDict()

    # -- wave evaluation ------------------------------------------------------
    def execute(self, items: Sequence[WorkItem]) -> int:
        """Launch + deposit one wave synchronously; returns items executed."""
        return self.deposit(self.launch(items))

    def launch(self, items: Sequence[WorkItem]) -> InFlightWave:
        """Dispatch all items to the device; no cache side effects.

        Items are deduplicated (two requests wanting the same round of
        the same stream cost one evaluation), folded into per-stream
        contiguous spans, and spans sharing a round count are evaluated
        by one fused multi-round launch per dimension bucket.  Nothing
        is dispatched after a bucket's kernel: its streams' sums are
        host views over the kernel's output, read in :meth:`deposit`, so
        this returns without waiting for the device.
        """
        obs = self.obs
        unique = sorted(set(items),
                        key=lambda it: (it.sampler, it.chash, it.round_index))
        groups: dict[tuple[str, int], list[_Span]] = {}
        for span in self._spans_of(unique):
            groups.setdefault((span.sampler, span.count), []).append(span)

        from repro.kernels import template
        launches_before = template.launch_count()
        plan_hits = {True: 0, False: 0, None: 0}
        results: list[tuple[CacheEntry, int, SumsState]] = []
        with obs.span("launch", items=len(unique),
                      groups=len(groups)) as span:
            self.faults.check("launch")
            for group_key in sorted(groups):
                out, hit = self._launch_group(groups[group_key], span)
                results.extend(out)
                plan_hits[hit] += 1
        obs.m["launches"].inc(template.launch_count() - launches_before)
        obs.m["plan_cache_hits"].inc(plan_hits[True])
        obs.m["plan_cache_misses"].inc(plan_hits[False])
        return InFlightWave(results=results, n_items=len(unique))

    def deposit(self, wave: InFlightWave) -> int:
        """Materialize a launched wave and group-commit it to the cache.

        Blocks once on the wave's device values, each fused bucket's
        stack once (wave k's transfer overlaps wave k+1's kernel when the
        engine pipelines); ``transfer`` then reads each stack with one
        device-to-host copy and cuts every (stream, round) out of it on
        the host, and each fallback round with three copies.  Every round
        folds through :meth:`ResultCache.deposit_wave` — one WAL fsync
        for the whole wave.  Returns the wave's item count.
        """
        obs = self.obs
        if _analysis.asserts_enabled():
            # STR002 live: no double-deposits or gaps within the wave
            per_stream: dict[str, list[int]] = {}
            for entry, round_index, _ in wave.results:
                per_stream.setdefault(entry.chash[:16],
                                      []).append(round_index)
            _analysis.assert_wave_consistent(per_stream)
        if wave.results:
            with obs.span("device_execute", items=wave.n_items):
                # block on the device futures *before* converting, so
                # the trace splits device wait from host-side transfer
                self.faults.check("device_execute")
                jax.block_until_ready(_device_values(wave.results))
        with obs.span("transfer", items=wave.n_items) as span:
            self.faults.check("transfer")
            with span.part("copies"):
                deposits = [
                    (entry, round_index,
                     SumsState(s1=self._to_host(sums.s1),
                               s2=self._to_host(sums.s2),
                               n=np.float32(self._to_host(sums.n))))
                    for entry, round_index, sums in wave.results]
            if (self.faults.enabled and deposits
                    and self.faults.fire("transfer_nan")):
                # poison the wave's first deposit: the cache's finite
                # check must reject it pre-journal and strike its stream
                entry, ri, sums = deposits[0]
                deposits[0] = (entry, ri, SumsState(
                    s1=np.full_like(sums.s1, np.nan),
                    s2=sums.s2, n=sums.n))
        with obs.span("deposit", items=wave.n_items):
            self.faults.check("deposit")
            self.cache.deposit_wave(deposits)
        return wave.n_items

    def _to_host(self, value) -> np.ndarray:
        """One field of a round's sums as f32 host data; a jax value is
        a device-to-host copy of its own (a fused bucket counts its one
        copy in :meth:`_FusedStack.rounds`)."""
        if isinstance(value, jax.Array):
            self.obs.m["d2h_copies"].inc()
        return np.asarray(value, np.float32)

    # -- wave shaping ---------------------------------------------------------
    def _spans_of(self, unique: Sequence[WorkItem]) -> list[_Span]:
        by_stream: dict[tuple[str, str], list[int]] = {}
        for it in unique:
            by_stream.setdefault((it.chash, it.sampler),
                                 []).append(it.round_index)
        spans = []
        for (chash, sampler) in sorted(by_stream):
            entry = self.cache.get(chash)
            if entry is None:
                raise KeyError(f"work item for unknown entry {chash}")
            rounds = sorted(by_stream[(chash, sampler)])
            if rounds != list(range(rounds[0], rounds[0] + len(rounds))):
                raise ValueError(
                    f"non-contiguous rounds {rounds} for stream "
                    f"{chash[:16]}: the planner must emit gap-free spans")
            spans.append(_Span(entry=entry, sampler=sampler,
                               start=rounds[0], count=len(rounds)))
        return spans

    def _launch_group(self, spans: list[_Span], trace_span):
        """One fused multi-round evaluation of same-count spans; its
        plan build and dispatch are parts of ``trace_span``.
        Returns the group's ``(entry, round, sums)`` and whether its
        fusion plan was cached (None when nothing was fused)."""
        n = self.cache.round_samples
        count = spans[0].count
        sampler = spans[0].sampler
        self.obs.m["wave_rounds"].observe(count, sampler=sampler)
        for sp in spans:
            self.obs.m["bucket_rounds"].inc(
                count, dim=sp.entry.family.dim, sampler=sampler)
        # streams the poison ladder degraded leave the fused path: they
        # re-run on the chunked per-round fallback, isolated from the
        # healthy buckets they shared a launch with (counter addressing
        # keeps the chunked recomputation bit-identical to the fused one)
        healthy = [sp for sp in spans if not sp.entry.degraded]
        degraded = [sp for sp in spans if sp.entry.degraded]

        stacks = ()
        hit = None
        if self.use_kernel and healthy:
            entries = [sp.entry for sp in healthy]
            fn_offsets = [e.fn_offset for e in entries]
            spec = MultiFunctionSpec(
                families=tuple(e.family for e in entries))
            from repro.kernels.mc_eval import multi
            self.faults.check("device_error")
            with trace_span.part("build"):
                plan, hit = self._plan_for(entries, sampler, spec,
                                           fn_offsets)
            start_rounds = {i: sp.start for i, sp in enumerate(healthy)}
            if self.mesh is not None:
                stacks = multi.sharded_eval_plan_rounds(
                    plan, n, count, self.key, self.mesh,
                    start_rounds=start_rounds, fn_axis=self.fn_axis,
                    sample_axes=self.sample_axes, part=trace_span.part)
            else:
                stacks = multi.eval_plan_rounds(
                    plan, n, count, self.key, start_rounds=start_rounds,
                    part=trace_span.part)

        fused = {}
        for sums, slices in stacks:
            stack = _FusedStack(sums, slices, n, self.obs.m["d2h_copies"])
            for sl in slices:
                fused[sl.family_index] = stack
        out = []
        for idx, sp in enumerate(healthy):
            if idx in fused:
                for r in range(count):
                    out.append((sp.entry, sp.start + r, SumsState(
                        s1=_HostView(fused[idx], idx, r, "s1"),
                        s2=_HostView(fused[idx], idx, r, "s2"),
                        n=np.float32(n))))
                continue
            out.extend(self._chunked_rounds(sp, count, n, sampler))
        for sp in degraded:
            out.extend(self._chunked_rounds(sp, count, n, sampler))
        return out, hit

    def _chunked_rounds(self, sp: _Span, count: int, n: int, sampler: str):
        """Chunked fallback: one counter-addressed eval per round."""
        self.fallback_rounds += count
        self.obs.m["fallback_rounds"].inc(count)
        out = []
        for r in range(count):
            sample_offset = (sp.start + r) * n
            if self.mesh is not None:
                sums, _ = direct_mc.sharded_family_sums(
                    sp.entry.family, n, self.key, self.mesh,
                    fn_axis=self.fn_axis, sample_axes=self.sample_axes,
                    fn_offset=sp.entry.fn_offset,
                    sample_offset=sample_offset, chunk=self.chunk,
                    use_kernel=self.use_kernel, sampler=sampler)
                sums = SumsState(s1=sums.s1[: sp.entry.n_fn],
                                 s2=sums.s2[: sp.entry.n_fn], n=sums.n)
            else:
                sums = direct_mc.family_sums(
                    sp.entry.family, n, self.key,
                    fn_offset=sp.entry.fn_offset,
                    sample_offset=sample_offset, chunk=self.chunk,
                    use_kernel=self.use_kernel, sampler=sampler)
            out.append((sp.entry, sp.start + r, sums))
        return out

    def _plan_for(self, entries: list[CacheEntry], sampler: str, spec,
                  fn_offsets):
        """LRU-cached fusion plan for this exact entry set, and whether
        it was cached.

        The plan holds packed per-entry operands, so the cache key is the
        entry identity tuple; eviction is least-recently-used (a full
        cache drops only the coldest mix, never the working set).  The
        *compiled* kernel behind a plan is shared by shape signature —
        see the module docstring.
        """
        from repro.kernels.mc_eval import multi
        plan_key = (tuple(e.chash for e in entries), sampler)
        plan = self._plans.get(plan_key)
        if plan is not None:
            self._plans.move_to_end(plan_key)
            return plan, True
        plan = multi.plan_spec(spec, sampler=sampler, fn_offsets=fn_offsets)
        self._plans[plan_key] = plan
        while len(self._plans) > self.plan_cache_size:
            self._plans.popitem(last=False)
        return plan, False


def _device_values(results) -> list:
    """What a wave's sums still wait on: each fused bucket's stack once,
    and the fallback rounds' own arrays."""
    out = {}
    for _, _, sums in results:
        for value in sums:
            if isinstance(value, _HostView):
                value = value.stack.device
            if isinstance(value, jax.Array):
                out[id(value)] = value
    return list(out.values())
