"""Columnar host-init parity and the pipelined run() surface.

The vectorized window/layer build (``Polisher._assemble_layers``: one
concatenated breaking-point matrix, vectorized span/PHRED filters,
argsort-by-window grouping) must produce windows IDENTICAL to the legacy
per-overlap/per-pair loop (kept as ``_build_windows_legacy``) — same
layer bytes, same qualities, same positions, same per-window layer order —
across strands, dummy-quality (FASTA) reads and fragment-correction-style
multi-overlap-per-query inputs. The fused ``run()`` must emit the same
polished sequences as initialize() + polish(), pipelined or via the
``num_threads=1`` sequential fallback.
"""

import random

import numpy as np
import pytest

from racon_tpu.core.overlap import (Overlap, bp_pairs_to_array,
                                    breaking_points_from_cigar)
from racon_tpu.core.polisher import Polisher, PolisherType
from racon_tpu.core.sequence import Sequence
from racon_tpu.core.window import WindowType
from racon_tpu.utils.cigar import parse_cigar


def make_polisher(window_length=100, quality_threshold=10.0,
                  type_=PolisherType.C, num_threads=1):
    # paths are never touched: sequences/overlaps are injected directly
    return Polisher("x.fasta", "x.paf", "x.fasta", type_, window_length,
                    quality_threshold, 0.3, True, 3, -5, -4, num_threads)


def random_cigar(rng, approx_len):
    ops = []
    total_t = 0
    while total_t < approx_len:
        op = rng.choices(["M", "I", "D"], weights=[8, 1, 1])[0]
        n = rng.randint(1, 25)
        ops.append(f"{n}{op}")
        if op in ("M", "D"):
            total_t += n
    return "".join(ops), total_t


def random_state(seed, window_length, with_quality=True, multi=False):
    """Targets + reads + overlaps whose breaking points come from real
    CIGAR walks (so every row satisfies the walker's invariants).
    ``multi`` makes several overlaps share a query read (the
    fragment-correction/ava shape)."""
    rng = random.Random(seed)
    nrng = np.random.default_rng(seed)
    bases = np.frombuffer(b"ACGT", np.uint8)

    targets = [Sequence(b"t%d" % i,
                        bases[nrng.integers(0, 4, rng.randint(
                            window_length * 2, window_length * 7))]
                        .tobytes())
               for i in range(3)]
    sequences = list(targets)
    overlaps = []
    n_reads = 12 if multi else 30
    for ri in range(n_reads):
        per_read = rng.randint(2, 3) if multi else 1
        read_len = rng.randint(window_length * 2, window_length * 5)
        data = bases[nrng.integers(0, 4, read_len)].tobytes()
        qual = (bytes(nrng.integers(33, 64, read_len).astype(np.uint8))
                if with_quality and ri % 3 else None)
        sequences.append(Sequence(b"r%d" % ri, data, qual))
        q_id = len(sequences) - 1
        for _ in range(per_read):
            t_id = rng.randrange(len(targets))
            t_len = len(targets[t_id].data)
            for _retry in range(20):
                cigar, t_span = random_cigar(rng, rng.randint(
                    window_length // 2,
                    min(t_len - 1, read_len - 30, window_length * 4)))
                q_span = sum(n for n, op in parse_cigar(cigar)
                             if op in ("M", "I"))
                if t_span < t_len and q_span <= read_len - 10:
                    break
            else:
                continue
            t_begin = rng.randint(0, t_len - t_span - 1)
            q_begin = rng.randint(0, read_len - q_span)
            o = Overlap()
            o.q_id = q_id
            o.t_id = t_id
            o.strand = rng.random() < 0.5
            o.q_begin, o.q_end = q_begin, q_begin + q_span
            o.q_length = read_len
            o.t_begin, o.t_end = t_begin, t_begin + t_span
            o.is_transmuted = True
            q_off = o.q_length - o.q_end if o.strand else o.q_begin
            o.breaking_points = bp_pairs_to_array(
                breaking_points_from_cigar(cigar, q_off, o.t_begin,
                                           o.t_end, window_length))
            overlaps.append(o)
    return sequences, len(targets), overlaps


def clone_overlaps(overlaps):
    out = []
    for o in overlaps:
        c = Overlap()
        c.q_id, c.t_id, c.strand = o.q_id, o.t_id, o.strand
        c.q_begin, c.q_end, c.q_length = o.q_begin, o.q_end, o.q_length
        c.t_begin, c.t_end = o.t_begin, o.t_end
        c.is_transmuted = True
        c.breaking_points = o.breaking_points.copy()
        out.append(c)
    return out


def build_with(p, sequences, n_targets, overlaps, legacy, **assemble_kw):
    p.sequences = list(sequences)
    p.targets_size = n_targets
    p._window_type = WindowType.TGS
    if legacy:
        p._build_backbone_windows()
        p._build_windows_legacy(overlaps)
    else:
        p._assemble_layers(overlaps, **assemble_kw)
    return p


def assert_windows_identical(pa, pb):
    assert len(pa.windows) == len(pb.windows)
    assert pa.targets_coverages == pb.targets_coverages
    for wa, wb in zip(pa.windows, pb.windows):
        assert (wa.id, wa.rank, wa.type) == (wb.id, wb.rank, wb.type)
        assert wa.sequences == wb.sequences
        assert wa.qualities == wb.qualities
        assert wa.positions == wb.positions


@pytest.mark.parametrize("seed", range(6))
def test_columnar_matches_legacy(seed):
    wl = [50, 100, 500][seed % 3]
    qthr = [10.0, 12.5][seed % 2]
    sequences, nt, overlaps = random_state(seed, wl)
    pa = build_with(make_polisher(wl, qthr), sequences, nt,
                    clone_overlaps(overlaps), legacy=False)
    pb = build_with(make_polisher(wl, qthr), sequences, nt,
                    clone_overlaps(overlaps), legacy=True)
    n_layers = sum(len(w.sequences) - 1 for w in pa.windows)
    n_rows = sum(len(o.breaking_points) for o in overlaps)
    assert 0 < n_layers <= n_rows
    assert_windows_identical(pa, pb)


def test_columnar_filters_fire_identically():
    """Both filters must actually drop rows (min-span and mean-PHRED),
    and drop the SAME rows in both paths."""
    sequences, nt, overlaps = random_state(11, 500, with_quality=True)
    pa = build_with(make_polisher(500, 43.0), sequences, nt,
                    clone_overlaps(overlaps), legacy=False)
    pb = build_with(make_polisher(500, 43.0), sequences, nt,
                    clone_overlaps(overlaps), legacy=True)
    n_layers = sum(len(w.sequences) - 1 for w in pa.windows)
    n_rows = sum(len(o.breaking_points) for o in overlaps)
    # qualities are uniform in [33, 64) (avg ~ 15): a 43.0 threshold
    # (avg >= 43 means raw mean >= 76) rejects every quality-bearing
    # read's rows, while the dummy-quality reads (ri % 3 == 0) pass
    assert 0 < n_layers < n_rows
    assert_windows_identical(pa, pb)


def test_columnar_matches_legacy_fragment_multi_overlap():
    """Fragment-correction shape: several overlaps per query read (mixed
    strands), like the PolisherType.F / ava-overlap inputs."""
    sequences, nt, overlaps = random_state(99, 100, multi=True)
    assert len({o.q_id for o in overlaps}) < len(overlaps)  # shared reads
    pa = build_with(make_polisher(100, type_=PolisherType.F), sequences,
                    nt, clone_overlaps(overlaps), legacy=False)
    pb = build_with(make_polisher(100, type_=PolisherType.F), sequences,
                    nt, clone_overlaps(overlaps), legacy=True)
    assert_windows_identical(pa, pb)


def test_columnar_matches_legacy_dummy_quality():
    """FASTA reads (quality None): the PHRED filter must not fire and the
    layers must carry None qualities, both paths."""
    sequences, nt, overlaps = random_state(7, 100, with_quality=False)
    assert all(s.quality is None for s in sequences)
    pa = build_with(make_polisher(100), sequences, nt,
                    clone_overlaps(overlaps), legacy=False)
    pb = build_with(make_polisher(100), sequences, nt,
                    clone_overlaps(overlaps), legacy=True)
    n_layers = sum(len(w.sequences) - 1 for w in pa.windows)
    assert n_layers > 0
    assert all(q is None for w in pa.windows for q in w.qualities[1:])
    assert_windows_identical(pa, pb)


def test_columnar_chunked_emit_matches_monolithic():
    """The run() producer's chunked emission (small chunk_windows, emit
    callback) must build the same windows as one monolithic pass, and the
    emitted ranges must tile [0, n_windows) in order."""
    sequences, nt, overlaps = random_state(3, 50)
    pa = build_with(make_polisher(50), sequences, nt,
                    clone_overlaps(overlaps), legacy=False)
    emitted = []
    pb = build_with(make_polisher(50), sequences, nt,
                    clone_overlaps(overlaps), legacy=False,
                    emit=lambda a, b: emitted.append((a, b)),
                    chunk_windows=3)
    assert_windows_identical(pa, pb)
    assert emitted[0][0] == 0 and emitted[-1][1] == len(pb.windows)
    assert all(e0[1] == e1[0] for e0, e1 in zip(emitted, emitted[1:]))
    assert len(emitted) > 1


def test_columnar_releases_breaking_points():
    sequences, nt, overlaps = random_state(5, 100)
    overlaps = clone_overlaps(overlaps)
    build_with(make_polisher(100), sequences, nt, overlaps, legacy=False)
    assert all(o.breaking_points is None for o in overlaps)


# ------------------------------------------------ prepare / finish halves

# (window_length, quality_threshold, type, random_state kwargs): the
# seeds and the filters-fire / fragment / dummy-quality cases above
PREPARE_CASES = {
    **{f"seed{s}": ([50, 100, 500][s % 3], [10.0, 12.5][s % 2],
                    PolisherType.C, dict(seed=s)) for s in range(6)},
    "filters_fire": (500, 43.0, PolisherType.C, dict(seed=11)),
    "fragment_multi": (100, 10.0, PolisherType.F,
                       dict(seed=99, multi=True)),
    "dummy_quality": (100, 10.0, PolisherType.C,
                      dict(seed=7, with_quality=False)),
}


def store_of(p):
    """The one LayerStore the polisher's covered windows share."""
    stores = {id(w.layer_view[0]): w.layer_view[0] for w in p.windows
              if w.layer_view[0] is not None}
    assert len(stores) == 1
    return next(iter(stores.values()))


def lanes_of(data, quality):
    """``weight << 3 | code`` of one layer from its bytes, the packer's
    definition written out independently of the pool."""
    code = np.full(len(data), 4, np.uint16)
    arr = np.frombuffer(data, np.uint8)
    for i, b in enumerate(b"ACGT"):
        code[arr == b] = i
    weight = (np.ones(len(data), np.uint16) if quality is None else
              np.maximum(np.frombuffer(quality, np.uint8).astype(np.int16)
                         - 33, 0).astype(np.uint16))
    return (weight << 3) | code


@pytest.mark.parametrize("case", sorted(PREPARE_CASES))
def test_prepared_ahead_inline_and_legacy_agree(case):
    """One prepare, two call times: started on its own thread before the
    assembly (as _initialize_core does beside the aligner) or run inline
    at the barrier. Both stores are equal field by field, every row
    addresses the bytes the legacy loop sliced, the packed lane blocks
    are those bytes' lanes, and the materialised windows are the
    legacy's."""
    wl, qthr, type_, kw = PREPARE_CASES[case]
    sequences, nt, overlaps = random_state(window_length=wl, **kw)

    def fresh():
        p = make_polisher(wl, qthr, type_)
        p.sequences = list(sequences)
        p.targets_size = nt
        p._window_type = WindowType.TGS
        return p, clone_overlaps(overlaps)

    pa, ova = fresh()
    pa._start_prepare(ova)
    pa._assemble_layers(ova)
    assert pa._prepare_ahead is None
    pb, ovb = fresh()
    pb._assemble_layers(ovb)
    pc, ovc = fresh()
    pc._build_backbone_windows()
    pc._build_windows_legacy(ovc)

    sa, sb = store_of(pa), store_of(pb)
    for field in ("pool", "qpool", "qpw_pool", "src", "length", "begin",
                  "end", "win_id", "has_qual", "row_bounds"):
        assert np.array_equal(getattr(sa, field), getattr(sb, field)), field
    # rows against the legacy loop's slices, window by window, BEFORE
    # anything materialises the lazy views
    Lq = int(sa.length.max())
    block = sa.gather_qpw(np.arange(sa.n_rows), Lq)
    assert np.array_equal(block, sb.gather_qpw(np.arange(sb.n_rows), Lq))
    r = 0
    for wi, wc in enumerate(pc.windows):
        assert sa.row_bounds[wi] == r
        for data, quality in zip(wc.sequences[1:], wc.qualities[1:]):
            s, ln = int(sa.src[r]), int(sa.length[r])
            assert sa.pool[s:s + ln].tobytes() == data
            assert bool(sa.has_qual[r]) == (quality is not None)
            if quality is not None:
                assert sa.qpool[s:s + ln].tobytes() == quality
            assert np.array_equal(block[r, :ln], lanes_of(data, quality))
            assert not block[r, ln:].any()
            r += 1
    assert r == sa.n_rows > 0
    assert_windows_identical(pa, pc)
    assert_windows_identical(pb, pc)


def test_read_with_every_row_filtered_is_pooled_and_changes_no_window():
    """The pool holds every overlap's read, not only the reads of rows
    that survive: a read whose rows all fail the mean-PHRED filter adds
    its bytes to the pool, shifts offsets, and leaves every window's
    layers as they are without it."""
    sequences, nt, overlaps = random_state(2, 100)
    victim = next(o for o in overlaps
                  if sequences[o.q_id].quality is not None)
    read = sequences[victim.q_id]
    sequences = list(sequences)
    sequences[victim.q_id] = Sequence(read.name, read.data,
                                      b'"' * len(read.data))  # Q1
    with_ = build_with(make_polisher(100), sequences, nt,
                       clone_overlaps(overlaps), legacy=False)
    without = build_with(
        make_polisher(100), sequences, nt,
        clone_overlaps([o for o in overlaps if o is not victim]),
        legacy=False)
    s1, s0 = store_of(with_), store_of(without)
    assert len(s1.pool) == len(s0.pool) + len(read.data)
    assert s1.n_rows == s0.n_rows
    assert not np.array_equal(s1.src, s0.src)       # offsets did shift
    Lq = int(s1.length.max())
    assert np.array_equal(s1.gather_qpw(np.arange(s1.n_rows), Lq),
                          s0.gather_qpw(np.arange(s0.n_rows), Lq))
    for w1, w0 in zip(with_.windows, without.windows):
        assert w1.sequences == w0.sequences
        assert w1.qualities == w0.qualities
        assert w1.positions == w0.positions


# ---------------------------------------------------------------- run()

def write_synthetic_assembly(tmp_path, seed=23, n_contigs=2, contig=3000):
    """Two-contig ~5x forward+reverse synthetic assembly on disk (the
    test_pipeline multi-target shape, plus reverse-strand reads)."""
    rng = np.random.default_rng(seed)
    bases = np.frombuffer(b"ACGT", np.uint8)
    comp = bytes.maketrans(b"ACGT", b"TGCA")

    def mutate(seq, rate):
        out = seq.copy()
        flips = rng.random(len(out)) < rate
        out[flips] = bases[rng.integers(0, 4, int(flips.sum()))]
        return out

    truths = [bases[rng.integers(0, 4, contig)] for _ in range(n_contigs)]
    backbones = [mutate(t, 0.06) for t in truths]
    layout = tmp_path / "layout.fasta"
    with open(layout, "wb") as f:
        for ti, bb in enumerate(backbones):
            f.write(b">ctg%d\n" % ti + bb.tobytes() + b"\n")
    reads_path = tmp_path / "reads.fastq"
    paf_path = tmp_path / "ovl.paf"
    with open(reads_path, "wb") as rf, open(paf_path, "wb") as pf:
        ri = 0
        for ti, truth in enumerate(truths):
            for start in range(0, contig - 600, 150):
                end = min(start + 900, contig)
                read = mutate(truth[start:end], 0.08)
                name = b"read%d" % ri
                strand = b"-" if ri % 3 == 0 else b"+"
                if strand == b"-":
                    read_bytes = read.tobytes().translate(comp)[::-1]
                else:
                    read_bytes = read.tobytes()
                rf.write(b"@" + name + b"\n" + read_bytes +
                         b"\n+\n" + b"9" * len(read) + b"\n")
                pf.write(b"\t".join([
                    name, b"%d" % len(read), b"0", b"%d" % len(read),
                    strand, b"ctg%d" % ti, b"%d" % contig, b"%d" % start,
                    b"%d" % end, b"%d" % (len(read) // 2),
                    b"%d" % len(read), b"255"]) + b"\n")
                ri += 1
    return reads_path, paf_path, layout


def polished_bytes(seqs):
    return [(s.name, s.data) for s in seqs]


def test_run_matches_initialize_polish(tmp_path):
    """Fused pipelined run() output == initialize() + polish() output
    (same bytes, names and order), with the pipelined path actually
    chunking (num_threads > 1)."""
    from racon_tpu.core.polisher import create_polisher

    rp, pp, lp = write_synthetic_assembly(tmp_path)
    ref = create_polisher(str(rp), str(pp), str(lp), num_threads=4)
    ref.initialize()
    want = polished_bytes(ref.polish(True))

    fused = create_polisher(str(rp), str(pp), str(lp), num_threads=4)
    got = polished_bytes(fused.run(True))
    assert got == want
    assert "build_windows_s" in fused.timings
    assert "align_s" in fused.timings
    assert "bp_decode_s" in fused.timings


def test_run_sequential_fallback_num_threads_1(tmp_path):
    """num_threads=1 takes the sequential initialize()/polish() path and
    must produce the same bytes as the pipelined run."""
    from racon_tpu.core.polisher import create_polisher

    rp, pp, lp = write_synthetic_assembly(tmp_path, seed=31)
    seq = create_polisher(str(rp), str(pp), str(lp), num_threads=1)
    got1 = polished_bytes(seq.run(True))

    par = create_polisher(str(rp), str(pp), str(lp), num_threads=4)
    got4 = polished_bytes(par.run(True))
    assert got1 == got4
    assert len(got1) == 2


def test_failed_initialize_leaves_object_reinitializable(tmp_path):
    """An alignment fault mid-init must leave self.windows empty so the
    double-init guard stays accurate and a retry rebuilds everything."""
    from racon_tpu.core.polisher import create_polisher

    rp, pp, lp = write_synthetic_assembly(tmp_path, seed=13, n_contigs=1,
                                          contig=1500)
    p = create_polisher(str(rp), str(pp), str(lp), num_threads=2)
    real_align = p.aligner.align_batch
    calls = {"n": 0}

    def flaky(pairs, *a, **kw):
        if calls["n"] == 0:
            calls["n"] += 1
            raise RuntimeError("injected aligner fault")
        return real_align(pairs, *a, **kw)

    p.aligner.align_batch = flaky
    with pytest.raises(RuntimeError, match="injected"):
        p.initialize()
    assert p.windows == []  # clean: retry is a real re-init, not a no-op
    p.initialize()
    assert len(p.windows) > 0
    assert len(p.polish(True)) == 1


def test_run_consensus_fault_retires_producer(tmp_path):
    """A consensus fault mid-stream must drain the bounded queue and join
    the producer before propagating (no stranded daemon thread)."""
    import threading

    from racon_tpu.core.polisher import create_polisher

    rp, pp, lp = write_synthetic_assembly(tmp_path, seed=17)
    p = create_polisher(str(rp), str(pp), str(lp), num_threads=4)
    p.consensus.run = lambda *a, **kw: (_ for _ in ()).throw(
        RuntimeError("injected consensus fault"))
    before = {t.name for t in threading.enumerate()}
    with pytest.raises(RuntimeError, match="injected"):
        p.run(True)
    leaked = [t for t in threading.enumerate()
              if t.name == "racon-layers" and t.is_alive()]
    assert not leaked, (before, leaked)


def test_double_initialize_warns_on_stderr(tmp_path, capsys):
    """The double-init warning must go to stderr: stdout carries the
    polished FASTA byte stream."""
    from racon_tpu.core.polisher import create_polisher

    rp, pp, lp = write_synthetic_assembly(tmp_path, seed=5, n_contigs=1,
                                          contig=1500)
    p = create_polisher(str(rp), str(pp), str(lp), num_threads=2)
    p.initialize()
    p.initialize()  # second call: warning, no rebuild
    cap = capsys.readouterr()
    assert "already initialized" in cap.err
    assert cap.out == ""


# ------------------------------------- prepare beside the aligner, in run()

def prepare_threads():
    import threading
    return [t for t in threading.enumerate()
            if t.name in ("racon-prepare", "racon-layers") and t.is_alive()]


def pool_counters():
    from racon_tpu.obs import metrics
    return (metrics.counter("build.pool_bytes"),
            metrics.counter("build.pool_bytes_ahead"))


@pytest.fixture(scope="module")
def assembly(tmp_path_factory):
    from racon_tpu.core.polisher import create_polisher

    tmp = tmp_path_factory.mktemp("prepare")
    paths = [str(x) for x in write_synthetic_assembly(tmp, seed=29)]
    want = polished_bytes(create_polisher(*paths, num_threads=1).run(True))
    assert len(want) == 2
    return paths, want


@pytest.mark.parametrize("surface,threads,ahead", [
    ("run", 4, "all"), ("split", 4, "all"), ("run", 4, "late"),
    ("run", 1, "inline"), ("split", 1, "inline")])
def test_pool_bytes_ahead_and_equal_fasta(assembly, monkeypatch, surface,
                                          threads, ahead):
    """Overlaps from a file and a thread to spare: prepare runs beside
    the aligner and all of the pool is ready at the barrier
    (``build.pool_bytes_ahead == build.pool_bytes``); a prepare still
    running at the barrier is waited for and counts nothing as ahead;
    ``num_threads == 1`` calls it inline (0). run() and initialize() +
    polish() give the same bytes every way."""
    import threading

    from racon_tpu.core.layers import LayerStore
    from racon_tpu.core.polisher import create_polisher

    paths, want = assembly
    p = create_polisher(*paths, num_threads=threads)
    real_align = p.aligner.align_batch
    real_prepare = LayerStore.prepare
    at_barrier = threading.Event()

    if ahead == "all":
        def align_after_prepare(pairs, *a, **kw):
            # the align phase outlasts prepare, as it does at real sizes
            p._prepare_ahead.thread.join(10)
            return real_align(pairs, *a, **kw)
        monkeypatch.setattr(p.aligner, "align_batch", align_after_prepare)
    elif ahead == "late":
        real_take = p._take_prepared

        def take(overlaps):
            at_barrier.set()
            return real_take(overlaps)

        def slow_prepare(*refs):
            assert at_barrier.wait(10)      # still running at the barrier
            return real_prepare(*refs)
        monkeypatch.setattr(p, "_take_prepared", take)
        monkeypatch.setattr(LayerStore, "prepare", staticmethod(slow_prepare))

    b0, a0 = pool_counters()
    if surface == "run":
        got = p.run(True)
    else:
        p.initialize()
        got = p.polish(True)
    b1, a1 = pool_counters()
    assert polished_bytes(got) == want
    assert b1 - b0 > 0
    assert a1 - a0 == (b1 - b0 if ahead == "all" else 0)
    assert p._prepare_ahead is None and not prepare_threads()


def test_streamed_auto_overlaps_prepare_inline(assembly):
    """--overlaps auto on the streamed chain feed: overlaps are still
    arriving while the aligner runs, so prepare is called at the
    barrier, where its work always was."""
    from racon_tpu.core.polisher import create_polisher
    from racon_tpu.io import parsers
    from racon_tpu.obs import metrics

    (reads, _, layout), _ = assembly
    b0, a0 = pool_counters()
    metrics.set_gauge("overlap.streamed", 0)
    p = create_polisher(reads, parsers.AUTO_OVERLAPS, layout, num_threads=4)
    assert len(p.run(True)) == 2
    assert metrics.gauge("overlap.streamed") == 1
    b1, a1 = pool_counters()
    assert b1 - b0 > 0 and a1 - a0 == 0
    assert not prepare_threads()


@pytest.mark.parametrize("surface", ["run", "initialize"])
def test_prepare_fault_surfaces_and_leaves_polisher_reinitializable(
        assembly, monkeypatch, surface):
    """An exception inside prepare (on its own thread) is re-raised on
    the thread that joins it and reaches the caller of run() /
    initialize(); no thread survives it, and the same object
    initializes again from scratch."""
    from racon_tpu.core.layers import LayerStore
    from racon_tpu.core.polisher import create_polisher

    paths, want = assembly
    p = create_polisher(*paths, num_threads=4)
    real_prepare = LayerStore.prepare

    def broken(*refs):
        raise RuntimeError("injected prepare fault")

    monkeypatch.setattr(LayerStore, "prepare", staticmethod(broken))
    with pytest.raises(RuntimeError, match="injected prepare fault"):
        p.run(True) if surface == "run" else p.initialize()
    assert not prepare_threads()
    assert p.windows == [] and p._prepare_ahead is None
    monkeypatch.setattr(LayerStore, "prepare", staticmethod(real_prepare))
    if surface == "run":
        got = p.run(True)
    else:
        p.initialize()
        got = p.polish(True)
    assert polished_bytes(got) == want


def test_failed_alignment_retires_the_prepare_thread(assembly):
    """The aligner fails while prepare runs beside it: the thread is
    joined before the fault propagates and nothing prepared is kept."""
    from racon_tpu.core.polisher import create_polisher

    paths, _ = assembly
    p = create_polisher(*paths, num_threads=4)

    def failing(pairs, *a, **kw):
        raise RuntimeError("injected aligner fault")

    p.aligner.align_batch = failing
    with pytest.raises(RuntimeError, match="injected aligner"):
        p.run(True)
    assert not prepare_threads()
    assert p.windows == [] and p._prepare_ahead is None
