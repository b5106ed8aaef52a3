"""Round-17 alignment occupancy: ragged pair packing (`_AlignStream`),
the adaptive band ladder, and the packed walk kernels — byte-identical
breaking points / CIGARs across the {bucketed, ragged} x {fixed-band,
ladder} grid.

The accept gate (``score <= band/2 - diff - 2``) is an optimality
certificate at every rung: any cell whose value can influence a
traceback decision is provably uninflated by the banding, so an
alignment accepted at a narrow rung IS the wide-band alignment, and the
ladder's terminal geometry sequence is the fixed path's — hence
identical accept/reject sets. This suite locks that contract on
randomized mixed-length/divergence pairs (escalation re-batching
included), the stream-feed-batching invariance the polisher relies on,
F-mode short reads, the empty-pair edges, OOM ``reduce_capacity``
re-dispatch parity, the align-stream warm-up cache claim, and the
``align.dispatch`` fault site's stall escalation through the exec
runner's degradation ladder. Wired as a fail-fast ci/cpu/test.sh shard
and re-run under RACON_TPU_SANITIZE=1 (the int32 shadow leg runs the
unpacked walk, covering the SWAR-packed walk kernel).
"""

import io
import pathlib

import numpy as np
import pytest

from racon_tpu import native
from racon_tpu.core.backends import NativeAligner, PythonAligner
from racon_tpu.obs import metrics
from racon_tpu.ops.nw import BAND_RUNGS, TpuAligner

BASES = np.frombuffer(b"ACGT", np.uint8)


def _fallback():
    return NativeAligner(2) if native.available() else PythonAligner()


def _engine(ragged=True, ladder=True, **kw):
    return TpuAligner(fallback=_fallback(), use_ragged=ragged,
                      use_ladder=ladder, **kw)


def _mixed_pairs(rng, n=48, lo=60, hi=1200, hot_every=9):
    """Randomized mixed workload spanning the (256, 128) and (1024, 384)
    buckets and several ladder rungs: low- and high-divergence pairs
    (the 50%-flip slice exceeds even the conservative TYPICAL-seeded rung,
    deterministically exercising the escalation re-batch path), indels
    for span asymmetry, one empty pair, plus overlap-filter-style error
    estimates."""
    pairs, errors = [], []
    for k in range(n):
        ln = int(rng.integers(lo, hi))
        t = BASES[rng.integers(0, 4, ln)]
        q = np.delete(t.copy(), rng.integers(0, ln, max(2, ln // 60)))
        div = 0.5 if k % hot_every == 0 else 0.03
        flips = rng.random(len(q)) < div
        q[flips] = BASES[rng.integers(0, 4, int(flips.sum()))]
        pairs.append((q.tobytes(), t.tobytes()))
        errors.append(1.0 - min(len(q), len(t)) / max(len(q), len(t)))
    pairs.append((b"", t.tobytes()))
    errors.append(0.0)
    pairs.append((b"ACGT", b""))
    errors.append(0.0)
    metas = [(k * 13 % 300, k * 7 % 200) for k in range(len(pairs))]
    return pairs, metas, errors


def _bp_equal(a, b):
    return len(a) == len(b) and all(
        np.array_equal(x, y) for x, y in zip(a, b))


@pytest.mark.parametrize("seed", range(2))
def test_grid_parity_randomized(seed):
    """{bucketed, ragged} x {fixed-band, ladder}: byte-identical CIGARs
    and breaking points; the ladder leg must actually seed narrow rungs
    and re-batch escapees, and its banded wavefront work must drop."""
    rng = np.random.default_rng(400 + seed)
    pairs, metas, errors = _mixed_pairs(rng)
    ref_cig = ref_bps = None
    work = {}
    for ragged in (False, True):
        for ladder in (False, True):
            eng = _engine(ragged, ladder)
            cig = eng.align_batch(pairs, errors=errors)
            bps = eng.breaking_points_batch(pairs, metas, 100,
                                            errors=errors)
            work[(ragged, ladder)] = eng.stats["wavefront_work"]
            if ref_cig is None:
                ref_cig, ref_bps = cig, bps
            else:
                assert cig == ref_cig, (ragged, ladder)
                assert _bp_equal(bps, ref_bps), (ragged, ladder)
            if ladder:
                assert eng.stats["ladder_narrow"] > 0
                assert eng.stats["band_escalated"] > 0  # 50%-flip slice
            assert eng.stats["lanes_occupied"] <= eng.stats["lanes_total"]
    assert any(len(b) for b in ref_bps)
    # the acceptance direction: ladder work strictly below fixed-band
    assert work[(True, True)] < work[(False, False)]


def test_stream_feed_batches_match_single_feed():
    """Polisher._align_need feeds the session in 64k slices; the slice
    boundaries must not change a single byte vs one monolithic feed
    (and vs the bucketed driver)."""
    rng = np.random.default_rng(77)
    pairs, metas, errors = _mixed_pairs(rng, n=30)
    ref = _engine(False, False).breaking_points_batch(
        pairs, metas, 100, errors=errors)

    eng = _engine()
    sess = eng.bp_stream(100, total=len(pairs))
    assert sess is not None
    for a in range(0, len(pairs), 7):
        sess.feed(pairs[a:a + 7], metas[a:a + 7], errors[a:a + 7])
    got = sess.finish()
    assert _bp_equal(got, ref)
    # every span copy and meta tuple released by the end of the session
    # (resolved slots release per chunk, rejects at finish)
    assert not sess.pairs and not sess.metas


def test_stream_empty_edges():
    """Empty feeds, empty pairs and a zero-pair finish must not wedge
    the drain loop."""
    eng = _engine()
    sess = eng.bp_stream(100)
    sess.feed([], [], [])
    assert sess.finish() == []

    sess2 = eng.bp_stream(100)
    sess2.feed([(b"", b"ACGT"), (b"AC", b"")], [(0, 0), (0, 0)],
               [0.0, 0.0])
    out = sess2.finish()
    assert len(out) == 2 and all(len(o) == 0 for o in out)

    # CIGAR-mode empties keep the wave driver's deletion/insertion codes
    cig = _engine().align_batch([(b"", b"ACGT"), (b"AC", b""), (b"", b"")])
    assert cig == ["4D", "2I", ""]


def test_f_mode_short_reads_parity():
    """F-mode shapes: very short pairs, all in the smallest bucket and
    the narrowest rungs — the regime that packs the most pairs per
    chunk."""
    rng = np.random.default_rng(31)
    pairs, metas, errors = _mixed_pairs(rng, n=40, lo=30, hi=90)
    ref = _engine(False, False).breaking_points_batch(
        pairs, metas, 50, errors=errors)
    eng = _engine()
    got = eng.breaking_points_batch(pairs, metas, 50, errors=errors)
    assert _bp_equal(got, ref)
    assert eng.stats["chunks"] >= 1


def _record_halves(eng, sess, events):
    """Wrap the engine's pack / submit / ``_finish_chunk`` so that each
    appends ``(what, chunk id)`` on entry and ``(what + "<", id)`` on
    return; a submit also records whether the session's in-flight bytes
    plus the new chunk's fit the budget at that moment."""
    ids = {}

    def cid(chunk):
        return ids.setdefault(id(chunk), len(ids))

    pack, submit, finish = (eng._pack_chunk, eng._submit_chunk,
                            eng._finish_chunk)

    def rec_pack(pairs, chunk, *a, **kw):
        events.append(("pack", cid(chunk)))
        out = pack(pairs, chunk, *a, **kw)
        events.append(("pack<", cid(chunk)))
        return out

    def rec_submit(packed):
        fits = (not sess.inflight
                or sess.inflight_bytes + packed.dirs_bytes
                <= eng.dirs_budget_cap)
        events.append(("submit", cid(packed.chunk), fits))
        out = submit(packed)
        events.append(("submit<", cid(packed.chunk)))
        return out

    def rec_finish(launched, *a, **kw):
        events.append(("finish", cid(launched[0])))
        finish(launched, *a, **kw)
        events.append(("finish<", cid(launched[0])))

    eng._pack_chunk, eng._submit_chunk, eng._finish_chunk = (
        rec_pack, rec_submit, rec_finish)


def _packed_ahead(events):
    """Chunk ids whose pack returned, then at least one earlier chunk
    was fetched, then their submit began — checking on the way that the
    three halves never nest and each chunk runs pack, submit, finish in
    that order."""
    ahead, open_what, stage = set(), None, {}
    fetched_since_pack = {}
    for what, c, *_ in events:
        if what.endswith("<"):
            assert open_what == (what[:-1], c), events
            open_what = None
            if what == "pack<":
                fetched_since_pack[c] = 0
            continue
        assert open_what is None, events     # no half inside another
        open_what = (what, c)
        assert stage.get(c) == {"pack": None, "submit": "pack",
                                "finish": "submit"}[what], events
        stage[c] = what
        if what == "finish":
            for k in fetched_since_pack:
                fetched_since_pack[k] += 1
        elif what == "submit" and fetched_since_pack.pop(c):
            ahead.add(c)
    assert open_what is None
    assert set(stage.values()) == {"finish"}
    return ahead


def test_pack_runs_ahead_of_the_fetch_and_submit_waits_for_room():
    """A budget that admits one full chunk at a time: chunk k+1 is
    packed while chunk k is still in flight, chunk k is fetched only
    then, and the puts and the dispatch of k+1 start after that fetch
    returned — never two chunks on the device that do not fit the
    budget together."""
    metrics.clear_run()
    rng = np.random.default_rng(91)
    pairs, metas, errors = _mixed_pairs(rng, n=40, lo=120, hi=250)
    ref = _engine(False, False).breaking_points_batch(
        pairs, metas, 100, errors=errors)
    metrics.clear_run()

    # eight pairs of the smallest bucket's longest sweep per chunk
    eng = _engine(ladder=False, max_dirs_bytes=8 * 512 * (128 // 8))
    sess = eng.bp_stream(100, total=len(pairs))
    events = []
    _record_halves(eng, sess, events)
    sess.feed(pairs, metas, errors)
    got = sess.finish()
    assert _bp_equal(got, ref)

    ahead = _packed_ahead(events)
    order = [c for what, c, *_ in events if what == "pack"]
    assert len(order) == eng.stats["chunks"] >= 5
    assert all(fits for what, _, *fits in events if what == "submit")
    # every chunk that found a chunk in flight it could not sit beside
    # was packed before that chunk's fetch began
    assert len(ahead) >= len(order) // 2
    for c in ahead:
        i_pack = events.index(("pack<", c))
        i_sub = next(i for i, e in enumerate(events)
                     if e[:2] == ("submit", c))
        between = events[i_pack + 1:i_sub]
        assert between and {w for w, *_ in between} == {"finish",
                                                        "finish<"}
        assert all(k < c for _, k in between)   # earlier chunks only
    # the counter that says the reorder engaged
    from racon_tpu import contracts
    assert "align.packed_ahead" in contracts.METRICS
    assert metrics.counter("align.packed_ahead") == len(ahead)
    assert metrics.counter("align.chunks") == len(order)


def test_packed_ahead_is_zero_where_the_budget_never_forces_a_fetch():
    """F-mode short reads under the default budget: chunks are cheap in
    direction-matrix bytes, nothing is fetched to make room, so pack and
    submit run back to back as before and the counter stays 0 (present:
    a ratio over ``align.chunks`` then reads 0, not absent)."""
    metrics.clear_run()
    rng = np.random.default_rng(31)
    pairs, metas, errors = _mixed_pairs(rng, n=40, lo=30, hi=90)
    eng = _engine()
    sess = eng.bp_stream(50, total=len(pairs))
    events = []
    _record_halves(eng, sess, events)
    sess.feed(pairs, metas, errors)
    sess.finish()
    assert eng.stats["chunks"] >= 1
    assert _packed_ahead(events) == set()
    assert metrics.counter("align.packed_ahead") == 0
    assert "align.packed_ahead" in metrics.snapshot()["counters"]
    # back to back: a pack's return is followed by its own submit
    for i, e in enumerate(events):
        if e[0] == "pack<":
            assert events[i + 1][:2] == ("submit", e[1])


def test_reduce_capacity_redispatch_parity():
    """The exec ladder's OOM-backpressure rung on the align arena: a
    capacity-halved engine re-dispatches smaller chunks with
    byte-identical breaking points (grouping never changes bytes)."""
    rng = np.random.default_rng(55)
    pairs, metas, errors = _mixed_pairs(rng, n=36)
    ref_eng = _engine()
    ref = ref_eng.breaking_points_batch(pairs, metas, 100, errors=errors)

    eng = _engine()
    for _ in range(4):
        assert eng.reduce_capacity()
    assert eng.capacity_scale == 16
    assert not eng.reduce_capacity()  # floor reached -> ladder falls on
    got = eng.breaking_points_batch(pairs, metas, 100, errors=errors)
    assert _bp_equal(got, ref)


def test_occupancy_telemetry_registry():
    """The round-17 counters land in BOTH the engine stats and the ONE
    metrics registry, and the derived pack summary is coherent (the
    run-report schema v6 / heartbeat pack[...] source)."""
    metrics.clear_run()
    rng = np.random.default_rng(13)
    pairs, metas, errors = _mixed_pairs(rng, n=24)
    eng = _engine()
    eng.breaking_points_batch(pairs, metas, 100, errors=errors)
    st = eng.stats
    assert 0 < st["lanes_occupied"] <= st["lanes_total"]
    assert st["steps_wasted"] == st["lanes_total"] - st["lanes_occupied"]
    assert st["wavefront_work"] > 0
    pm = eng.pack_metrics()
    assert 0 < pm["align_pack_efficiency"] <= 1
    assert abs(pm["align_pack_efficiency"] + pm["align_pad_fraction"]
               - 1) < 1e-6
    assert metrics.counter("align.chunks") == st["chunks"]
    assert metrics.counter("align.lanes_total") == st["lanes_total"]
    pack = metrics.pack_summary()
    for key in ("align_pack_efficiency", "align_pad_fraction",
                "align_chunks", "align_steps_wasted"):
        assert key in pack
    assert pack["align_chunks"] == st["chunks"]
    from racon_tpu.exec.heartbeat import pack_summary_str
    assert f"{st['chunks']}c" in pack_summary_str()


def test_adaptive_ladder_learns_divergence():
    """A substitution-heavy run whose span-asymmetry estimates read
    near zero initially seeds low and escapes; once ADAPT_MIN_PAIRS
    accepted pairs are observed, seeds incorporate the realized
    divergence and later chunks stop escaping."""
    from racon_tpu.ops import nw as nw_mod

    eng = _engine()
    # feed the observer directly (unit-level: the estimator, not a
    # full 256-pair device run)
    eng._observe_divergence([20] * nw_mod.ADAPT_MIN_PAIRS,
                            [100] * nw_mod.ADAPT_MIN_PAIRS)
    ad = eng._adaptive_divergence()
    assert ad is not None and abs(ad - 0.2) < 1e-6
    # a misleading near-zero span estimate is floored by observation
    assert eng._est_divergence(0.0) >= 0.2
    # seeds quantize to a declared rung (or the bucket band)
    g = eng._seed_geometry(500, 500, 0.0)
    assert g is not None
    band = g[1]
    assert band in BAND_RUNGS or band == eng.buckets[g[0]][1]


def test_warmup_precompiles_align_stream_shapes():
    """The align warm-up derives the stream's chunk geometry: after
    warm-up, a matching live dispatch adds ZERO new compiles on the
    forward, traceback and breaking-points kernels (the round-13
    consensus warm-up test's claim, on the aligner)."""
    from racon_tpu import sanitize
    from racon_tpu.ops import nw as nw_mod

    if sanitize.enabled():
        pytest.skip("the sanitizer's int32 shadow leg compiles the "
                    "unpacked twin of every first chunk by design — "
                    "the cache-count claim holds for the production "
                    "path only")
    eng = _engine()
    th = eng.warmup_async(200, 8, window_length=100)
    assert th is not None
    th.join(timeout=300)
    assert not th.is_alive()
    # repeat calls with the same geometry are free (shape dedupe)
    assert eng.warmup_async(200, 8, window_length=100) is None
    cached = (nw_mod._nw_wavefront_kernel._cache_size(),
              nw_mod._traceback_kernel._cache_size(),
              nw_mod._breaking_points_kernel._cache_size())
    assert cached[0] >= 1

    # live pairs matching the warmed geometry: equal lengths (need ==
    # 16 like the estimate), the estimate's 0.05 error class, 8 pairs
    rng = np.random.default_rng(5)
    pairs = []
    for _ in range(8):
        t = BASES[rng.integers(0, 4, 200)]
        q = t.copy()
        flips = rng.random(200) < 0.02
        q[flips] = BASES[rng.integers(0, 4, int(flips.sum()))]
        pairs.append((q.tobytes(), t.tobytes()))
    bps = eng.breaking_points_batch(pairs, [(0, 0)] * 8, 100,
                                    errors=[0.05] * 8)
    assert sum(len(b) > 0 for b in bps) == 8
    assert (nw_mod._nw_wavefront_kernel._cache_size(),
            nw_mod._traceback_kernel._cache_size(),
            nw_mod._breaking_points_kernel._cache_size()) == cached, \
        "live dispatch missed the warmed shapes (recompiled)"


def test_polisher_stream_feed_byte_identity(tmp_path):
    """End-to-end through create_polisher with an injected off-mesh
    device aligner: the polisher's sliced session feed must produce the
    same polished FASTA as the bucketed fixed-band driver, and the
    dispatch-vs-fetch split must land in the init breakdown."""
    from test_columnar_init import write_synthetic_assembly

    from racon_tpu.core.polisher import create_polisher
    from racon_tpu.obs import trace as obs_trace

    rp, pp, lp = write_synthetic_assembly(pathlib.Path(tmp_path), seed=7,
                                          n_contigs=2, contig=2000)
    obs_trace.activate(tracing=False)  # arm span timers

    def run(**al_kw):
        p = create_polisher(str(rp), str(pp), str(lp), num_threads=4,
                            aligner=_engine(**al_kw))
        out = b"".join(b">" + s.name + b"\n" + s.data + b"\n"
                       for s in p.run(True))
        return out, dict(p.timings)

    ref, timings = run()
    assert "align_dispatch_s" in timings and "align_fetch_s" in timings
    assert timings["align_dispatch_s"] > 0 or timings["align_fetch_s"] > 0
    got, _ = run(ragged=False, ladder=False)
    assert got == ref


def test_align_dispatch_stall_escalates_runner_ladder(tmp_path,
                                                     monkeypatch):
    """The new align.dispatch fault site: an injected stall during the
    align phase surfaces as a StallError, classifies 'stall' and walks
    the shard down the exec runner's degradation ladder (CPU retry)
    with the merged output still correct."""
    from test_columnar_init import write_synthetic_assembly

    from racon_tpu import faults
    from racon_tpu.core.polisher import create_polisher
    from racon_tpu.exec import ShardRunner

    rp, pp, lp = write_synthetic_assembly(pathlib.Path(tmp_path), seed=9,
                                          n_contigs=2, contig=2000)
    p = create_polisher(str(rp), str(pp), str(lp), num_threads=4)
    want = b"".join(b">" + s.name + b"\n" + s.data + b"\n"
                    for s in p.run(True))

    monkeypatch.setenv("RACON_TPU_FAULTS", "align.dispatch:stall")
    faults.reset()
    try:
        runner = ShardRunner(str(rp), str(pp), str(lp),
                             work_dir=str(tmp_path / "work"),
                             num_threads=4, n_shards=2,
                             aligner_backend="tpu")
        buf = io.BytesIO()
        summary = runner.run(buf)
    finally:
        monkeypatch.delenv("RACON_TPU_FAULTS", raising=False)
        faults.reset()
    assert buf.getvalue() == want
    atts = [a for e in summary["shards"]
            for a in (e.get("attempts") or [])]
    assert any(a["class"] == "stall" for a in atts), atts
