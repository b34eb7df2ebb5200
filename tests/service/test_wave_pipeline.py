"""Wave pipeline: multi-round fused launches, group commit, fairness.

The tentpole invariant is **bit-identity**: an R-round wave evaluated by
one fused multi-round launch per bucket deposits per-round sums that are
bit-for-bit the sums of R single-round launches — so the cache's
in-order fold, resume and persistence guarantees are untouched while the
launch count drops from R x B to B.  These tests assert that digest
equality end to end (kernel, chunked and sharded paths, the pipelined
worker, and crash replay through the group-committed WAL), plus the
planner's round-robin fairness and the batcher's LRU plan cache.

Engine construction and the bit-identity assertion come from the shared
``tests/service/conftest.py`` fixtures (``make_engine`` defaults to
rounds of ``R`` samples).
"""

import threading

import jax
import numpy as np
import pytest

from repro.core import gaussian_family, harmonic_family
from repro.core import rng as rng_lib
from repro.kernels import template
from repro.kernels.mc_eval import multi
from repro.service import (IntegrationClient, IntegrationRequest)

R = 4096   # = conftest.R, the make_engine fixture's round quantum


# -- kernel layer: one launch == R launches, bit for bit ----------------------

@pytest.mark.parametrize("sampler", ["mc", "sobol"])
def test_eval_plan_rounds_bit_identical(sampler):
    from repro.core import MultiFunctionSpec
    spec = MultiFunctionSpec.from_families(
        [harmonic_family(6, 3), gaussian_family(4, 3)])
    plan = multi.plan_spec(spec, sampler=sampler)
    key = rng_lib.fold_key(4, 0)
    fused = multi.split_rounds(multi.eval_plan_rounds(
        plan, R, 3, key, start_rounds={0: 0, 1: 0}), R)
    for r in range(3):
        single = multi.eval_plan(plan, R, key, sample_offset=r * R)
        for fam in single:
            np.testing.assert_array_equal(np.asarray(fused[fam][r].s1),
                                          np.asarray(single[fam].s1))
            np.testing.assert_array_equal(np.asarray(fused[fam][r].s2),
                                          np.asarray(single[fam].s2))


def test_eval_plan_rounds_heterogeneous_starts():
    """Streams parked at different depths share one launch."""
    from repro.core import MultiFunctionSpec
    spec = MultiFunctionSpec.from_families(
        [harmonic_family(6, 3), gaussian_family(4, 3)])
    plan = multi.plan_spec(spec)
    key = rng_lib.fold_key(4, 0)
    fused = multi.split_rounds(multi.eval_plan_rounds(
        plan, R, 2, key, start_rounds={0: 2, 1: 0}), R)
    for fam, start in ((0, 2), (1, 0)):
        for r in range(2):
            single = multi.eval_plan(plan, R, key,
                                     sample_offset=(start + r) * R)
            np.testing.assert_array_equal(np.asarray(fused[fam][r].s1),
                                          np.asarray(single[fam].s1))


def test_sharded_eval_plan_rounds_bit_identical():
    from repro.core import MultiFunctionSpec
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    spec = MultiFunctionSpec.from_families(
        [harmonic_family(6, 3), gaussian_family(4, 3)])
    plan = multi.plan_spec(spec)
    key = rng_lib.fold_key(4, 0)
    starts = {0: 1, 1: 0}
    sharded = multi.split_rounds(multi.sharded_eval_plan_rounds(
        plan, R, 2, key, mesh, start_rounds=starts), R)
    fused = multi.split_rounds(multi.eval_plan_rounds(
        plan, R, 2, key, start_rounds=starts), R)
    for fam in fused:
        for r in range(2):
            np.testing.assert_array_equal(np.asarray(sharded[fam][r].s1),
                                          np.asarray(fused[fam][r].s1))
            np.testing.assert_array_equal(np.asarray(sharded[fam][r].s2),
                                          np.asarray(fused[fam][r].s2))


# -- batcher layer: one host copy per fused bucket ----------------------------

def _wave_of(engine, fams, starts, count, degraded=()):
    """Entries for ``fams`` in ``engine``'s cache (those indexed in
    ``degraded`` marked so), and ``count`` rounds of each from its start."""
    from repro.service.batcher import WorkItem
    entries = [engine.cache.get_or_allocate(f"{i:064x}", fam)
               for i, fam in enumerate(fams)]
    for i in degraded:
        entries[i].degraded = True
    items = [WorkItem(e.chash, s + r, "mc")
             for e, s in zip(entries, starts) for r in range(count)]
    return entries, items


def _single_round(engine, fams, entries, family_index, round_index):
    """The round as one single-round fused launch computes it."""
    from repro.core import MultiFunctionSpec
    plan = multi.plan_spec(MultiFunctionSpec(families=tuple(fams)),
                           fn_offsets=[e.fn_offset for e in entries])
    return multi.eval_plan(plan, R, engine.key,
                           sample_offset=round_index * R)[family_index]


def test_batcher_host_split_bit_identical_to_single_round_launches(
        make_engine):
    """Several streams over two dim buckets, three rounds each from
    different depths: every (stream, round) cut from the bucket stacks on
    the host equals its own single-round launch, bit for bit."""
    engine = make_engine()
    fams = [harmonic_family(6, 3), gaussian_family(4, 3),
            harmonic_family(5, 2)]
    starts = (0, 2, 1)
    entries, items = _wave_of(engine, fams, starts, 3)
    template.reset_launch_count()
    wave = engine.batcher.launch(items)
    assert template.launch_count() == 2
    got = {(e.chash, r): s for e, r, s in wave.results}
    assert len(got) == 9
    for i, (entry, start) in enumerate(zip(entries, starts)):
        for r in range(start, start + 3):
            single = _single_round(engine, fams, entries, i, r)
            sums = got[(entry.chash, r)]
            np.testing.assert_array_equal(np.asarray(sums.s1),
                                          np.asarray(single.s1))
            np.testing.assert_array_equal(np.asarray(sums.s2),
                                          np.asarray(single.s2))
            assert float(sums.n) == float(single.n) == R


def test_launch_returns_device_futures_only(make_engine, monkeypatch):
    """``launch`` dispatches nothing after the kernel: a fused stream's
    sums are host views over the kernel's own output.  ``deposit`` then
    reads each fused bucket with one copy, and a degraded stream's
    chunked rounds with three copies each."""
    outputs = []
    kernel = template.fused_mc_pallas

    def recording(*args, **kwargs):
        out = kernel(*args, **kwargs)
        outputs.append(out)
        return out

    monkeypatch.setattr(template, "fused_mc_pallas", recording)
    engine = make_engine()
    fams = [harmonic_family(6, 3), gaussian_family(4, 3),
            harmonic_family(5, 2), harmonic_family(3, 3)]
    count = 2
    entries, items = _wave_of(engine, fams, (0, 0, 0, 0), count,
                              degraded=(3,))
    wave = engine.batcher.launch(items)
    stacks = set()
    for entry, _, sums in wave.results:
        if entry.degraded:
            assert all(isinstance(v, jax.Array) for v in sums)
            continue
        assert not any(isinstance(v, jax.Array) for v in sums)
        assert isinstance(sums.n, np.float32)
        assert sums.s1.stack is sums.s2.stack
        assert any(sums.s1.stack.device is out for out in outputs)
        stacks.add(id(sums.s1.stack))
    assert len(stacks) == 2            # the dim-2 and dim-3 buckets
    copies = engine.obs.m["d2h_copies"]
    before = copies.value()
    assert engine.batcher.deposit(wave) == 4 * count
    assert copies.value() - before == len(stacks) + 3 * count
    assert [e.rounds_done for e in entries] == [count] * 4


def _as_host_triples(results, shift=0.0):
    """``results`` rewritten as the benchmark's fault planting does:
    host copies of every field, ``s1`` moved by ``shift``."""
    from repro.core.direct_mc import SumsState
    return [(entry, r, SumsState(
        s1=np.asarray(s.s1, np.float32).copy() + np.float32(shift),
        s2=np.asarray(s.s2, np.float32).copy(), n=np.asarray(s.n)))
        for entry, r, s in results]


def test_rewritten_results_are_what_deposit_folds(make_engine):
    engine = make_engine()
    fams = [harmonic_family(6, 3), harmonic_family(5, 2)]
    entries, items = _wave_of(engine, fams, (0, 0), 2)
    wave = engine.batcher.launch(items)
    copies = engine.obs.m["d2h_copies"]
    before = copies.value()
    wave.results = _as_host_triples(wave.results, shift=1.0)
    assert copies.value() - before == 2   # the rewrite read each bucket
    engine.batcher.deposit(wave)
    assert copies.value() - before == 2   # host triples cost no copy
    for entry in entries:
        mine = [s for e, _, s in wave.results if e is entry]
        np.testing.assert_array_equal(entry.s1, mine[0].s1 + mine[1].s1)
        assert entry.rounds_done == 2


def test_transfer_nan_on_rewritten_fused_wave_is_not_journaled(
        make_engine, tmp_path):
    from repro.service.faults import FaultPlan
    from repro.service.store import read_journal
    engine = make_engine(state_dir=str(tmp_path),
                         faults=FaultPlan({"transfer_nan": 0}))
    fams = [harmonic_family(6, 3), harmonic_family(5, 2)]
    entries, items = _wave_of(engine, fams, (0, 0), 2)
    wave = engine.batcher.launch(items)
    wave.results = _as_host_triples(wave.results)
    poisoned = wave.results[0][0]
    engine.batcher.deposit(wave)
    assert poisoned.rounds_done == 0 and poisoned.poison_strikes == 1
    healthy = [e for e in entries if e is not poisoned]
    assert [e.rounds_done for e in healthy] == [2]
    records, _ = read_journal(engine.store.journal_path)
    journaled = [rec["chash"] for rec in records if rec["t"] == "dep"]
    assert poisoned.chash not in journaled
    assert journaled == [healthy[0].chash] * 2
    engine.close()


# -- engine layer: multi-round waves == single-round waves --------------------

@pytest.mark.parametrize("use_kernel", [True, False])
def test_multiround_wave_matches_per_round_waves(make_engine, bit_identical,
                                                 use_kernel):
    """R rounds in one wave (one launch) == R single-round waves."""
    fams = [harmonic_family(4, 3), gaussian_family(3, 2)]
    fused_engine = make_engine(use_kernel=use_kernel, max_rounds_per_wave=8)
    template.reset_launch_count()
    fused = IntegrationClient(fused_engine).integrate(fams, n_samples=4 * R)
    fused_launches = template.launch_count()

    per_engine = make_engine(use_kernel=use_kernel, max_rounds_per_wave=1)
    template.reset_launch_count()
    per = IntegrationClient(per_engine).integrate(fams, n_samples=4 * R)
    per_launches = template.launch_count()

    bit_identical(fused, per)
    if use_kernel:
        # 4 rounds x 2 dim buckets: 8 launches -> 2
        assert fused_launches == 2
        assert per_launches == 8
    assert fused_engine.stats.waves == 1
    assert per_engine.stats.waves == 4


def test_multiround_wave_on_mesh_bit_identical(make_engine, bit_identical):
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    fams = [harmonic_family(4, 3)]
    fused = IntegrationClient(make_engine(mesh=mesh,
                                          max_rounds_per_wave=8)).integrate(
        fams, n_samples=3 * R)
    per = IntegrationClient(make_engine(mesh=mesh,
                                        max_rounds_per_wave=1)).integrate(
        fams, n_samples=3 * R)
    bit_identical(fused, per)


def test_mixed_depth_streams_fuse_into_one_launch(make_engine,
                                                  bit_identical):
    """A top-up and a cold stream with equal round counts share a launch."""
    engine = make_engine(max_rounds_per_wave=8)
    cli = IntegrationClient(engine)
    cli.integrate([harmonic_family(4, 3)], n_samples=R)    # depth 1
    t1 = engine.submit(IntegrationRequest.make(
        [harmonic_family(4, 3)], n_samples=3 * R))         # rounds [1, 3)
    t2 = engine.submit(IntegrationRequest.make(
        [gaussian_family(4, 3)], n_samples=2 * R))         # rounds [0, 2)
    template.reset_launch_count()
    while engine.step():
        pass
    # same count, same dim, different stream depths -> ONE launch
    assert template.launch_count() == 1
    res_h, res_g = engine.poll(t1), engine.poll(t2)

    clean = make_engine(max_rounds_per_wave=8)
    ref_h = IntegrationClient(clean).integrate([harmonic_family(4, 3)],
                                               n_samples=3 * R)
    ref_g = IntegrationClient(clean).integrate([gaussian_family(4, 3)],
                                               n_samples=2 * R)
    bit_identical(res_h, ref_h)
    bit_identical(res_g, ref_g)


def test_pipelined_worker_bit_identical_to_sync(make_engine, bit_identical):
    """Double-buffered waves deposit exactly what serial waves deposit."""
    fams = [harmonic_family(4, 3), gaussian_family(3, 2)]
    piped = make_engine(max_rounds_per_wave=2, pipeline_waves=True)
    piped.start()
    try:
        cli = IntegrationClient(piped)
        res = cli.wait(cli.submit(fams, n_samples=6 * R), timeout=300.0)
    finally:
        piped.stop()
    assert piped.stats.waves >= 2          # the budget spans several waves

    sync = make_engine(max_rounds_per_wave=2)
    ref = IntegrationClient(sync).integrate(fams, n_samples=6 * R)
    bit_identical(res, ref)


def test_pipelined_worker_many_clients(make_engine):
    """Concurrent submitters against the pipelined worker: all served,
    overlapping asks deduped onto shared streams, estimates sane."""
    from repro.core import harmonic_analytic
    engine = make_engine(max_rounds_per_wave=2, pipeline_waves=True)
    engine.start()
    results = {}

    def client(i):
        results[i] = IntegrationClient(engine).integrate(
            [harmonic_family(4, 2 + i % 2)], n_samples=4 * R)

    try:
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300.0)
    finally:
        engine.stop()
    assert len(results) == 6
    assert engine.cache.n_entries == 2         # dims 2 and 3 shared
    # clients sharing a stream get the identical fold
    for i in (0, 1):
        np.testing.assert_array_equal(results[i].means,
                                      results[i + 2].means)
        np.testing.assert_array_equal(results[i].means,
                                      results[i + 4].means)
        exact = harmonic_analytic(4, 2 + i)
        assert np.all(np.abs(results[i].means - exact)
                      <= 6 * results[i].stderrs + 1e-6)


# -- group commit + crash replay ----------------------------------------------

def test_group_commit_one_journal_write_per_wave(make_engine, tmp_path):
    """A 4-round wave journals its deposits in ONE write+fsync."""
    from repro.service.store import DurableStore
    writes = []
    orig = DurableStore._write

    def counting_write(self, record):
        writes.append(len(record))
        return orig(self, record)

    DurableStore._write = counting_write
    try:
        engine = make_engine(state_dir=str(tmp_path), max_rounds_per_wave=8)
        IntegrationClient(engine).integrate([harmonic_family(4, 3)],
                                            n_samples=4 * R)
    finally:
        DurableStore._write = orig
    # one alloc record + one group-committed batch of 4 deposit records
    assert len(writes) == 2
    assert engine.cache.get(
        next(iter(engine.cache._entries))).rounds_done == 4


def test_torn_group_commit_replays_prefix(make_engine, bit_identical,
                                          tmp_path):
    """A crash tearing the wave's batch write loses only a round suffix;
    the restart tops up bit-identically."""
    from repro.service.store import _MAGIC, DurableStore
    engine = make_engine(state_dir=str(tmp_path), max_rounds_per_wave=8)
    IntegrationClient(engine).integrate([harmonic_family(6, 3)],
                                        n_samples=3 * R)
    # no close(): the journal is all that survives the "SIGKILL"; tear
    # the batch at the last record boundary (drop deposit r2)
    import os
    journal = os.path.join(str(tmp_path), DurableStore.JOURNAL)
    with open(journal, "rb") as f:
        data = f.read()
    starts = []
    pos = 0
    while (pos := data.find(_MAGIC, pos)) != -1:
        starts.append(pos)
        pos += len(_MAGIC)
    assert len(starts) == 4                  # alloc + 3 deposits
    with open(journal, "wb") as f:
        f.write(data[:starts[3] + 7])        # torn mid-record

    e2 = make_engine(state_dir=str(tmp_path), max_rounds_per_wave=8)
    assert e2.cache.recovered.truncated_bytes > 0
    template.reset_launch_count()
    res = IntegrationClient(e2).integrate([harmonic_family(6, 3)],
                                          n_samples=3 * R)
    assert e2.stats.items_executed == 1      # only the torn round re-paid
    assert template.launch_count() == 1
    clean = IntegrationClient(
        make_engine(max_rounds_per_wave=8)).integrate(
            [harmonic_family(6, 3)], n_samples=3 * R)
    bit_identical(res, clean)


def test_transient_deposit_failure_replays_wave(make_engine, bit_identical,
                                                tmp_path):
    """A wave whose group commit dies mid-write is replayed identically
    (journaled prefix replays as exact no-ops on the retry)."""
    engine = make_engine(state_dir=str(tmp_path), max_rounds_per_wave=8)
    store = engine.store
    orig = store.append_deposits
    fails = {"left": 1}

    def flaky(payloads):
        payloads = list(payloads)
        if fails["left"]:
            fails["left"] -= 1
            orig(payloads[:1])               # half the batch hits disk...
            raise OSError("injected torn group commit")
        return orig(payloads)

    store.append_deposits = flaky
    res = IntegrationClient(engine).integrate([harmonic_family(4, 3)],
                                              n_samples=3 * R)
    assert engine.stats.restarts == 1
    clean = IntegrationClient(
        make_engine(max_rounds_per_wave=8)).integrate(
            [harmonic_family(4, 3)], n_samples=3 * R)
    bit_identical(res, clean)


def test_deposit_wave_skips_ahead_of_frontier_rounds():
    """A wave carrying rounds whose predecessors are still in another
    driver's in-flight wave folds (and journals) nothing for them; the
    planner re-schedules once the frontier catches up.  The single-round
    deposit keeps its strict gap-raise contract."""
    from repro.core.direct_mc import SumsState
    from repro.service import ResultCache
    cache = ResultCache(round_samples=R)
    entry = cache.get_or_allocate("x:mc", harmonic_family(4, 3))
    ones = SumsState(s1=np.ones(4, np.float32),
                     s2=np.ones(4, np.float32), n=np.float32(R))
    assert cache.deposit_wave([(entry, 1, ones)]) == 0   # round 0 missing
    assert entry.rounds_done == 0
    assert cache.deposit_wave([(entry, 0, ones), (entry, 1, ones)]) == 2
    assert entry.rounds_done == 2
    assert cache.deposit_wave([(entry, 1, ones)]) == 0   # replay: skipped
    with pytest.raises(ValueError, match="deposit gap"):
        cache.deposit(entry, 3, ones)


# -- fairness -----------------------------------------------------------------

def test_small_request_not_starved_by_heavy(make_engine):
    """Round-robin wave budget: the small ask completes in wave 1 even
    though a heavy ask arrived first and wants far more than the wave."""
    engine = make_engine(max_rounds_per_wave=4, max_items_per_wave=4)
    heavy = engine.submit(IntegrationRequest.make(
        [harmonic_family(4, 3)], n_samples=16 * R))
    small = engine.submit(IntegrationRequest.make(
        [gaussian_family(4, 2)], n_samples=R))
    assert engine.step()
    assert engine.poll(small) is not None, "small request starved"
    assert engine.poll(heavy) is None
    while engine.step():
        pass
    assert engine.poll(heavy) is not None


def test_greedy_allocation_would_starve_rr_does_not(make_engine):
    """With many heavy streams saturating the budget, every stream still
    progresses every wave (one round each, round-robin)."""
    engine = make_engine(max_rounds_per_wave=8, max_items_per_wave=3)
    tickets = [engine.submit(IntegrationRequest.make(
        [harmonic_family(2, 2 + i % 3)], n_samples=2 * R)) for i in range(3)]
    engine.step()
    done = [e.rounds_done for pend in engine._pending.values()
            for e in pend.entries]
    # budget 3 over 3 streams -> exactly one round each, nobody at 2
    assert len(done) == 3 and all(d == 1 for d in done)
    while engine.step():
        pass
    assert all(engine.poll(t) is not None for t in tickets)


# -- plan cache ---------------------------------------------------------------

def test_plan_cache_lru_eviction(make_engine):
    engine = make_engine()
    batcher = engine.batcher
    batcher.plan_cache_size = 2
    cli = IntegrationClient(engine)
    fams = [harmonic_family(4, d) for d in (2, 3, 4)]
    for f in fams:
        cli.integrate([f], n_samples=R)
    assert len(batcher._plans) == 2          # oldest mix evicted
    keys = list(batcher._plans)
    # a warm re-ask costs no launches, so the plan table is untouched
    cli.integrate([fams[2]], n_samples=R)
    assert list(batcher._plans) == keys
    # re-planning the evicted mix displaces the least recently used
    cli.integrate([fams[0]], n_samples=2 * R)
    assert len(batcher._plans) == 2
    assert keys[0] not in batcher._plans


def test_plan_reused_across_waves(make_engine):
    """A topped-up stream re-uses its cached plan object (LRU hit)."""
    engine = make_engine(max_rounds_per_wave=1)
    cli = IntegrationClient(engine)
    cli.integrate([harmonic_family(4, 3)], n_samples=2 * R)  # two waves
    assert len(engine.batcher._plans) == 1
