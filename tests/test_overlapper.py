"""First-party overlapper suite (``--overlaps auto``): randomized
kernel-vs-numpy-oracle parity for both stages (minimizer seeding and
chain DP), strand canonicalization, the slice-boundary dedup, the
resident fetch path, frequency-cap accounting, warm-up shape caching,
and the end-to-end determinism contract — auto-mode polish output
byte-identical across thread counts and ``--shards 2``, gz/FASTQ/FASTA
input variants producing identical auto PAFs, F mode, and the
planner/rampler no-overlaps-file cases.
"""

import gzip
import io
import pathlib

import numpy as np
import pytest

from test_columnar_init import write_synthetic_assembly

from racon_tpu.core.polisher import PolisherType, create_polisher
from racon_tpu.exec import ShardRunner
from racon_tpu.exec.index import build_index_readsonly, write_auto_paf
from racon_tpu.exec.planner import estimate_job_cost
from racon_tpu.io import parsers
from racon_tpu.models import overlap as reference
from racon_tpu.obs import metrics
from racon_tpu.ops import chain, overlap_seed

REPO_ROOT = str(pathlib.Path(__file__).resolve().parents[1])
_ACGT = np.frombuffer(b"ACGT", np.uint8)
_COMP = bytes.maketrans(b"ACGT", b"TGCA")


def rand_seq(rng, n):
    return rng.choice(_ACGT, size=n).astype(np.uint8).tobytes()


def revcomp(s):
    return s.translate(_COMP)[::-1]


def table_rows(table):
    h, i, p, s = table
    return list(zip(i.tolist(), p.tolist(), h.tolist(),
                    np.asarray(s, bool).tolist()))


# ------------------------------------------------- stage 1: minimizers

def test_minimizer_matches_numpy_oracle():
    """The jit'd minimizer kernel agrees with the pure-numpy oracle
    exactly — randomized lengths, several (k, w) geometries, ambiguous
    bases included."""
    rng = np.random.default_rng(11)
    for k, w in ((15, 5), (11, 3), (8, 7), (4, 1)):
        for trial in range(4):
            n = int(rng.integers(k + w - 1, 3000))
            seq = bytearray(rand_seq(rng, n))
            if trial % 2:  # sprinkle ambiguity
                for j in rng.integers(0, n, size=max(1, n // 50)):
                    seq[int(j)] = ord(b"N")
            seq = bytes(seq)
            got = table_rows(overlap_seed.build_seed_table(
                [seq], k=k, w=w))
            want = [(0, p, h, bool(s))
                    for h, p, s in reference.minimizers_np(seq, k, w)]
            assert got == want, (k, w, trial, n)


def test_minimizer_strand_canonical():
    """Reverse-complementing a sequence yields the same canonical hash
    multiset with mirrored positions (p -> L - k - p) and flipped
    strand bits — the property seed matching across strands rests on."""
    rng = np.random.default_rng(12)
    k, w = 15, 5
    seq = rand_seq(rng, 1200)
    fwd = reference.minimizers_np(seq, k, w)
    rev = reference.minimizers_np(revcomp(seq), k, w)
    L = len(seq)
    # windowed selection differs at the edges, but every interior
    # minimizer must appear mirrored; compare the intersection both ways
    fset = {(h, p, s) for h, p, s in fwd}
    rset = {(h, p, s) for h, p, s in rev}
    mirrored = {(h, L - k - p, 1 - s) for h, p, s in rev}
    assert len(fset & mirrored) >= int(0.9 * min(len(fset), len(rset)))
    assert {h for h, _, _ in fset} == {h for h, _, _ in mirrored}


def test_minimizer_slice_boundary_dedup(monkeypatch):
    """Long sequences are seeded in bounded overlapping slices; a
    minimizer selected by windows on both sides of a slice boundary
    must emit ONCE. Shrinking the arena's row forces many boundaries
    through a short sequence so the dedup is exercised cheaply."""
    rng = np.random.default_rng(13)
    seq = rand_seq(rng, 700)
    want = table_rows(overlap_seed.build_seed_table([seq]))
    monkeypatch.setattr(overlap_seed, "SEED_ROW", 96)
    got = table_rows(overlap_seed.build_seed_table([seq]))
    assert got == want


def test_seed_table_skips_short_sequences():
    rng = np.random.default_rng(15)
    k, w = 15, 5
    table = overlap_seed.build_seed_table(
        [b"ACGT", rand_seq(rng, 400), b""], k=k, w=w)
    assert set(table[1].tolist()) == {1}


# ------------------------------------------- stage 1 as a stream of arenas

STREAM_LENGTHS = (150, 700, 90, 400, 1000, 60, 333, 511, 96, 820)


def _stream_seqs(seed=16):
    rng = np.random.default_rng(seed)
    seqs = [bytearray(rand_seq(rng, n)) for n in STREAM_LENGTHS]
    seqs[3][200] = ord(b"N")       # an ambiguous base in a sliced read
    return [bytes(s) for s in seqs]


def _small_arenas(monkeypatch, batch=4, row=96):
    monkeypatch.setattr(overlap_seed, "SEED_ROW", row)
    monkeypatch.setattr(overlap_seed, "SEED_BATCH", batch)


def _oracle_rows(seqs, k=15, w=5):
    return [(i, p, h, bool(s)) for i, seq in enumerate(seqs)
            for h, p, s in reference.minimizers_np(seq, k, w)]


def test_seed_stream_many_arenas_equal_the_oracle_and_one_arena(monkeypatch):
    """Rows of 96 bases in arenas of 4: ten sequences fill 14 arenas,
    sliced sequences lie across arena boundaries, and seam repeats
    arise inside an arena and between two — the streamed table equals
    ``minimizers_np`` and the one-arena build entry for entry."""
    seqs = _stream_seqs()
    _small_arenas(monkeypatch)
    chunks = list(overlap_seed._iter_chunks(seqs, 15, 5))
    assert len(chunks) >= 4 * 4
    # a repeat: the last slot one slice picked is the first of the next
    picks = [[off + p for _, p, _ in reference.minimizers_np(blob, 15, 5)]
             for _, off, blob, _ in chunks]
    seams = [r for r in range(1, len(chunks))
             if chunks[r][0] == chunks[r - 1][0] and picks[r]
             and picks[r - 1] and picks[r][0] == picks[r - 1][-1]]
    assert any(r % 4 == 0 for r in seams), "no repeat between two arenas"
    assert any(r % 4 != 0 for r in seams), "no repeat inside an arena"
    assert any(chunks[r][0] == chunks[r - 1][0]
               for r in range(4, len(chunks), 4))
    got = table_rows(overlap_seed.build_seed_table(seqs))
    assert got == _oracle_rows(seqs)
    monkeypatch.setattr(overlap_seed, "SEED_BATCH", 64)
    assert len(chunks) <= 64
    assert table_rows(overlap_seed.build_seed_table(seqs)) == got


def test_seed_stream_orders_by_arena_not_by_completion(monkeypatch):
    """Arena 0's compaction is held until arena 1's has finished (and
    the interpreter switches threads as often as it can): the slices
    lie by arena index, so the table is the oracle's all the same."""
    import sys
    import threading
    seqs = _stream_seqs(17)
    _small_arenas(monkeypatch)
    compact = overlap_seed._compact_arena
    second_done = threading.Event()
    finished = []

    def held(out, offset, *rest):
        if offset == 0:
            assert second_done.wait(30), "arena 1 never finished"
        n = compact(out, offset, *rest)
        finished.append(offset)
        if offset:
            second_done.set()
        return n

    monkeypatch.setattr(overlap_seed, "_compact_arena", held)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        got = table_rows(overlap_seed.build_seed_table(seqs))
    finally:
        sys.setswitchinterval(interval)
    assert len(finished) >= 4 and finished[0] != 0 and 0 in finished
    assert got == _oracle_rows(seqs)


def test_seed_table_out_of_order_chunks_take_the_sort(monkeypatch):
    """Chunks that do not arrive in ``(seq_id, offset)`` order reach
    the sort fallback and leave in canonical order, a seam's repeat
    dropped wherever its twin landed."""
    seqs = _stream_seqs(18)
    _small_arenas(monkeypatch)
    want = table_rows(overlap_seed.build_seed_table(seqs))
    in_order = overlap_seed._iter_chunks
    sorts = []
    canonical = overlap_seed._canonical
    monkeypatch.setattr(overlap_seed, "_iter_chunks",
                        lambda *a: reversed(list(in_order(*a))))
    monkeypatch.setattr(overlap_seed, "_canonical",
                        lambda table: sorts.append(1) or canonical(table))
    assert table_rows(overlap_seed.build_seed_table(seqs)) == want
    assert sorts == [1]
    assert want == _oracle_rows(seqs)


def test_seed_stream_without_the_native_core(monkeypatch):
    """With the native core reported absent the numpy row copy and the
    ``np.nonzero`` compaction build the same table."""
    from racon_tpu import native
    seqs = _stream_seqs(19)
    _small_arenas(monkeypatch)
    assert native.available()
    want = table_rows(overlap_seed.build_seed_table(seqs))
    monkeypatch.setattr(native, "available", lambda: False)
    monkeypatch.setattr(native, "compact_seed_rows", None)
    monkeypatch.setattr(native, "copy_byte_rows", None)
    assert table_rows(overlap_seed.build_seed_table(seqs)) == want
    assert want == _oracle_rows(seqs)


def test_compact_seed_rows_refuses_what_it_cannot_place():
    """The native compaction checks before it writes: planes of another
    dtype or shape, a slice that leaves the table, and more selected
    slots than the rows' counts sum to all raise."""
    from racon_tpu import native
    sel = np.zeros((2, 11), bool)
    sel[0, [1, 10]] = sel[1, 3] = True
    h = np.arange(22, dtype=np.uint32).reshape(2, 11)
    ids, offs = np.array([0, 1]), np.array([0, 0])

    def table(n):
        return (np.zeros(n, np.uint32), np.zeros(n, np.int32),
                np.zeros(n, np.int32), np.zeros(n, bool))

    out = table(4)
    assert native.compact_seed_rows(h, sel, sel, ids, offs, [2, 1],
                                    out, 1, 3) == 3
    assert out[2].tolist() == [0, 1, 10, 3]
    assert out[0].tolist() == [0, 1, 10, 14]
    with pytest.raises(ValueError):
        native.compact_seed_rows(h.astype(np.int64), sel, sel, ids, offs,
                                 [2, 1], table(4), 0, 3)
    with pytest.raises(ValueError):
        native.compact_seed_rows(h, sel[:, :10], sel, ids, offs, [2, 1],
                                 table(4), 0, 3)
    with pytest.raises(IndexError):
        native.compact_seed_rows(h, sel, sel, ids, offs, [2, 1],
                                 table(4), 2, 3)
    with pytest.raises(ValueError):
        native.compact_seed_rows(h, sel, sel, ids, offs, [2, 1],
                                 table(4), 0, 2)


@pytest.mark.parametrize("batch", [4, 64])
def test_seed_stream_counts_its_arenas(monkeypatch, batch):
    """``overlap.seed_arenas`` is the arenas launched;
    ``overlap.seed_arenas_ahead`` all but the first of a streamed
    build, and none where one arena leaves nothing to pack ahead of."""
    seqs = _stream_seqs(20)
    _small_arenas(monkeypatch, batch=batch)
    arenas = -(-len(list(overlap_seed._iter_chunks(seqs, 15, 5))) // batch)
    assert arenas == (1 if batch == 64 else 14)
    before = [metrics.counter("overlap.seed_arenas"),
              metrics.counter("overlap.seed_arenas_ahead")]
    overlap_seed.build_seed_table(seqs)
    assert metrics.counter("overlap.seed_arenas") - before[0] == arenas
    ahead = metrics.counter("overlap.seed_arenas_ahead") - before[1]
    assert ahead == arenas - 1


def test_seed_stream_spans_are_declared_and_workers_take_no_idle(
        assembly, tmp_path, monkeypatch):
    """A job whose 32 reads fill two seeding arenas, through the CLI
    with a report and a trace: every ``overlap.seed*`` span it opened
    is declared; what the stream's workers opened is timer-only, so the
    ledger charges them nothing and ``idle_overlap_s``' listed timers
    (with ``idle.overlap.filter``, the ingest's) are still all of the
    overlapper's idle."""
    import json

    from racon_tpu import cli, contracts
    from racon_tpu.obs import trace
    rp, _, lp = assembly
    monkeypatch.setattr(overlap_seed, "SEED_BATCH", 16)
    overlap_seed.clear_table_cache()
    rep, tr = tmp_path / "report.json", tmp_path / "trace.json"
    try:
        rc = cli.main(["-t", "2", "--overlaps", "auto", "--run-report",
                       str(rep), "--trace", str(tr), str(rp), "auto",
                       str(lp)])
    finally:
        trace.deactivate()
    assert rc == 0
    report = json.loads(rep.read_bytes())
    counters = report["metrics"]["counters"]
    # two arenas of reads, one of the two contigs
    assert counters["overlap.seed_arenas"] == 3
    assert counters["overlap.seed_arenas_ahead"] == 1
    timers = report["metrics"]["timers"]
    opened = {n for n in timers if n.startswith("overlap.seed")}
    assert {"overlap.seed", "overlap.seed.pack", "overlap.seed.dispatch",
            "overlap.seed.fetch", "overlap.seed.get",
            "overlap.seed.compact"} == opened <= contracts.SPANS
    events = json.loads(tr.read_bytes())["traceEvents"]
    names = {e["tid"]: e["args"]["name"] for e in events
             if e["name"] == "thread_name"}
    workers = {e["name"] for e in events if e["ph"] == "X"
               and names[e["tid"]].startswith("racon-seedstream")}
    assert workers == {"overlap.seed.get", "overlap.seed.compact"}
    assert workers <= contracts.TIMER_ONLY_SPANS
    listed = set(json.loads(pathlib.Path(
        REPO_ROOT, "benchmark/metrics/idle_overlap_s.json"
    ).read_bytes())["spans"]) | {"idle.overlap.filter"}
    idle = {n for n in timers if n.startswith("idle.overlap")}
    assert idle <= listed
    dt = report["device_time"]
    assert sum(dt["idle_by"].values()) == pytest.approx(dt["idle_s"],
                                                        abs=1e-4)
    # the three arenas (and the warm-up's dummy, where it ran)
    assert dt["by_program"]["_minimizer_kernel"]["count"] in (3, 4)


# --------------------------------------------------- stage 2: chain DP

def test_chain_kernel_matches_numpy_oracle():
    """The banded chain DP kernel reproduces the integer numpy oracle
    bit-exactly over randomized seed sets (score, seed count, and the
    chained span)."""
    rng = np.random.default_rng(21)
    k = 15
    for S in (16, 64):
        B = chain._pair_batch(S)
        ts = np.zeros((B, S), np.int32)
        qs = np.zeros((B, S), np.int32)
        ns = np.zeros(B, np.int32)
        for lane in range(3):
            n = int(rng.integers(S // 2, S + 1))
            t = np.sort(rng.integers(0, 4000, n)).astype(np.int32)
            q = (t + rng.integers(-300, 300, n)).clip(0).astype(np.int32)
            ts[lane, :n], qs[lane, :n], ns[lane] = t, q, n
        out = np.asarray(chain._chain_kernel(ts, qs, ns, S=S, k=k))
        for lane in range(3):
            n = int(ns[lane])
            want = reference.chain_np(ts[lane, :n], qs[lane, :n], k)
            assert out[lane].tolist() == list(want), (S, lane)


def test_find_overlaps_exact_spans():
    """Reads cut verbatim from a target map back to their exact source
    spans with the right strand (forward and reverse-complement)."""
    rng = np.random.default_rng(22)
    target = rand_seq(rng, 8000)
    fwd = target[1000:4000]
    rev = revcomp(target[4500:7500])
    rows = chain.find_overlaps([fwd, rev], [target],
                               np.full(2, -1, np.int64),
                               k=15, w=5, max_occ=64, min_seeds=4)
    for q, strand, t_lo, t_hi in ((0, 0, 1000, 4000),
                                  (1, 1, 4500, 7500)):
        mine = np.flatnonzero(rows["q_ord"] == q)
        assert mine.size == 1
        i = int(mine[0])
        assert int(rows["strand"][i]) == strand
        assert abs(int(rows["t_begin"][i]) - t_lo) < 40
        assert abs(int(rows["t_end"][i]) - t_hi) < 40
        span = int(rows["q_end"][i]) - int(rows["q_begin"][i])
        assert span > 2800


def test_find_overlaps_suppresses_self_hits():
    """C-mode self suppression: a read that IS target j emits no row
    against j, but still maps to other targets."""
    rng = np.random.default_rng(23)
    t0 = rand_seq(rng, 3000)
    t1 = t0[:2000] + rand_seq(rng, 1000)  # shares a 2 kb prefix
    rows = chain.find_overlaps([t0], [t0, t1],
                               np.array([0], np.int64), k=15, w=5)
    assert 0 not in rows["t_idx"].tolist()
    assert 1 in rows["t_idx"].tolist()


def test_freq_cap_accounting():
    """Buckets hotter than max_occ drop WHOLE and are counted — never
    silently; raising the cap readmits them."""
    rng = np.random.default_rng(24)
    motif = rand_seq(rng, 400)
    reads = [motif] * 12  # every minimizer bucket has 12+12 entries
    rt = overlap_seed.build_seed_table(reads)
    tt = overlap_seed.build_seed_table(reads)
    self_t = np.full(12, -1, np.int64)
    qlens = np.full(12, 400, np.int64)
    hits, capped = reference.match_seeds(rt, tt, self_t, qlens,
                                     k=15, max_occ=4)
    assert capped > 0 and hits["q"].size == 0
    hits2, capped2 = reference.match_seeds(rt, tt, self_t, qlens,
                                       k=15, max_occ=64)
    assert capped2 == 0 and hits2["q"].size > 0


def test_min_seeds_drop_accounting():
    """Pairs under the min_seeds floor are dropped and counted, both
    pre-DP (candidate too small) and post-DP (chain too small)."""
    rng = np.random.default_rng(25)
    target = rand_seq(rng, 4000)
    reads = [target[500:2500], rand_seq(rng, 2000)]
    rows_loose = chain.find_overlaps(reads, [target],
                                     np.full(2, -1, np.int64),
                                     k=15, w=5, min_seeds=4)
    rows_tight = chain.find_overlaps(reads, [target],
                                     np.full(2, -1, np.int64),
                                     k=15, w=5, min_seeds=10 ** 6)
    assert rows_loose["q_ord"].size > 0
    assert rows_tight["q_ord"].size == 0


# ------------------------------------------- stage 1.5: device seed join

def rand_table(rng, n_seqs, n_entries, hash_space):
    """A synthetic minimizer table with a deliberately tiny hash space
    (dense cross-table collisions) — deduped on (seq, pos) exactly like
    ``build_seed_table``, the property that makes the join's 5-tuples
    unique and the device sort's tie-break freedom harmless."""
    sid = rng.integers(0, n_seqs, n_entries).astype(np.int32)
    pos = rng.integers(0, 4000, n_entries).astype(np.int32)
    order = np.lexsort((pos, sid))
    sid, pos = sid[order], pos[order]
    keep = np.ones(sid.size, bool)
    keep[1:] = (sid[1:] != sid[:-1]) | (pos[1:] != pos[:-1])
    sid, pos = sid[keep], pos[keep]
    h = rng.integers(0, hash_space, sid.size).astype(np.uint32)
    strand = rng.integers(0, 2, sid.size).astype(bool)
    return h, sid, pos, strand


def pooled_table(rng, n_seqs, n_entries, pool, weights=None):
    """:func:`rand_table` with its hashes drawn from ``pool`` — mixed
    32-bit values, as the seed builder's are, so the low bits the
    join's prefilter reads are spread."""
    _, sid, pos, strand = rand_table(rng, n_seqs, n_entries, 2)
    return rng.choice(pool, sid.size, p=weights), sid, pos, strand


def _mixed_hashes(rng, n):
    return rng.choice(1 << 32, n, replace=False).astype(np.uint32)


def _dense_case(rng):
    """Tiny hash space: dense cross-table collisions (what the
    prefilter does with hashes this crowded is not asserted)."""
    n_reads = int(rng.integers(2, 10))
    n_targets = int(rng.integers(1, 6))
    hash_space = int(rng.integers(20, 300))
    rt = rand_table(rng, n_reads, int(rng.integers(50, 600)), hash_space)
    tt = rand_table(rng, n_targets, int(rng.integers(50, 600)), hash_space)
    return rt, tt, int(rng.integers(2, 40)), None


def _planted_tables(rng, distinct, alone, share, n_reads, read_entries,
                    n_targets, target_entries):
    """A draft table over ``distinct`` mixed hashes and a read table of
    which ``share`` draws from them, the rest from ``alone`` others."""
    shared, others = _mixed_hashes(rng, distinct), _mixed_hashes(rng, alone)
    tt = pooled_table(rng, n_targets, target_entries, shared)
    w = np.r_[np.full(distinct, share / distinct),
              np.full(alone, (1 - share) / alone)]
    rt = pooled_table(rng, n_reads, read_entries, np.r_[shared, others], w)
    return rt, tt


def _engaged_case(rng):
    """A read table much larger than the draft's, a few per cent of its
    entries planted from the draft's hashes: the prefilter compacts."""
    rt, tt = _planted_tables(rng, 240, 20_000, 0.04, 40, 30_000, 3, 700)
    return rt, tt, int(rng.integers(8, 40)), True


def _collisions_case(rng):
    """So many distinct draft hashes that thousands of read entries pass
    the presence table on low bits they only share (one slot in 32 is
    taken) and the kernel's search has to turn them down."""
    rt, tt = _planted_tables(rng, 2_000, 50_000, 0.02, 60, 80_000, 4, 6_000)
    matching = int(np.isin(rt[0], tt[0]).sum())
    assert chain._present_reads(rt[0], np.unique(tt[0])).size \
        > matching + 1000
    return rt, tt, 64, True


def _hot_by_reads_case(rng):
    """Buckets of two or three draft entries that the reads' own
    occurrences carry over ``max_occ`` (the ramp's ``tr``): they drop
    whole, and only kept entries can have made them hot."""
    rt, tt = _planted_tables(rng, 30, 8_000, 0.08, 30, 6_000, 2, 80)
    return rt, tt, 16, True


def _all_vs_all_case(rng):
    """``-f``: the target table IS the read table, every entry's bits
    are present, nothing is compacted."""
    rt = pooled_table(rng, 12, 3_000, _mixed_hashes(rng, 1_500))
    return rt, rt, 8, False


_JOIN_COUNTERS = ("overlap.join_bailouts", "overlap.join_read_entries",
                  "overlap.join_read_kept")


def _join_counted(rt, tt, self_t, qlens, max_occ):
    """``join_seeds`` on the device path and what it added to
    :data:`_JOIN_COUNTERS`: ``(hits, capped, bailed, offered, kept)``."""
    before = [metrics.counter(name) for name in _JOIN_COUNTERS]
    hits, capped = chain.join_seeds(rt, tt, self_t, qlens, k=15,
                                    max_occ=max_occ, device_join=True)
    return (hits, capped, *(metrics.counter(name) - was
                            for name, was in zip(_JOIN_COUNTERS, before)))


def _assert_same_hits(got, want, note=None):
    for key in ("q", "t", "rel", "tp", "qc"):
        assert np.array_equal(np.asarray(got[key], np.int64),
                              want[key]), (note, key)


@pytest.mark.parametrize("case, trials", [
    (_dense_case, 8), (_engaged_case, 3), (_collisions_case, 1),
    (_hot_by_reads_case, 3), (_all_vs_all_case, 2)],
    ids=lambda v: v.__name__.strip("_") if callable(v) else None)
def test_device_join_matches_oracle(case, trials):
    """The device seed join (host prefilter, look-up + ramp kernel,
    ragged expand kernel) reproduces the numpy ``match_seeds`` oracle
    exactly — both strands, self-hit suppression and hot-bucket capping
    included, the prefilter engaged and stood aside — with zero
    bail-outs to the oracle."""
    rng = np.random.default_rng(31)
    for trial in range(trials):
        rt, tt, max_occ, engaged = case(rng)
        n_reads, n_targets = int(rt[1].max()) + 1, int(tt[1].max()) + 1
        self_t = np.where(rng.random(n_reads) < 0.3,
                          rng.integers(0, n_targets, n_reads),
                          -1).astype(np.int64)
        qlens = rng.integers(4100, 6000, n_reads).astype(np.int64)
        want, capped_w = reference.match_seeds(rt, tt, self_t, qlens,
                                           k=15, max_occ=max_occ)
        got, capped_g, bailed, offered, kept = _join_counted(
            rt, tt, self_t, qlens, max_occ)
        assert capped_g == capped_w, trial
        _assert_same_hits(got, want, trial)
        assert bailed == 0 and offered == rt[0].size
        if engaged is not None:
            assert (kept * 4 < offered) if engaged else (kept == offered)
        if case is _hot_by_reads_case:
            assert capped_g > 0 and want["q"].size > 0


def test_join_prefilter_counts_and_reads_the_kept_entries(monkeypatch):
    """``overlap.join_read_kept`` never passes ``.join_read_entries``,
    and the bail-out ladder's table rung reads the kept entries: a read
    table whose own padding would not fit :data:`JOIN_TABLE_CELLS`
    beside the draft's stays on the device when what can match does."""
    rng = np.random.default_rng(34)
    rt, tt, max_occ, _ = _engaged_case(rng)
    self_t = np.full(int(rt[1].max()) + 1, -1, np.int64)
    qlens = np.full(self_t.size, 5000, np.int64)
    want, capped_w = reference.match_seeds(rt, tt, self_t, qlens, k=15,
                                           max_occ=max_occ)
    assert chain._table_pad(rt[0].size) == 1 << 15
    monkeypatch.setattr(chain, "JOIN_TABLE_CELLS", 1 << 14)
    got, capped_g, bailed, offered, kept = _join_counted(
        rt, tt, self_t, qlens, max_occ)
    assert bailed == 0 and 0 < kept <= offered == rt[0].size
    assert chain._table_pad(kept) + chain._table_pad(tt[0].size) <= 1 << 14
    assert capped_g == capped_w and want["q"].size > 0
    _assert_same_hits(got, want)
    # a draft table that alone fills the arena still bails, counted,
    # and such a join offers the device nothing
    monkeypatch.setattr(chain, "JOIN_TABLE_CELLS", 1 << 10)
    _, _, bailed, offered, kept = _join_counted(rt, tt, self_t, qlens,
                                                max_occ)
    assert (bailed, offered, kept) == (1, 0, 0)


def test_device_join_empty_side_bails_to_oracle():
    """An empty table on either side takes the counted bail-out rung —
    the oracle's trivial path, never a kernel launch."""
    rng = np.random.default_rng(33)
    rt = rand_table(rng, 4, 200, 100)
    empty = (np.zeros(0, np.uint32), np.zeros(0, np.int32),
             np.zeros(0, np.int32), np.zeros(0, bool))
    before = metrics.counter("overlap.join_bailouts")
    hits, capped = chain.join_seeds(rt, empty, np.full(4, -1, np.int64),
                                    np.full(4, 5000, np.int64),
                                    k=15, max_occ=64, device_join=True)
    assert hits["q"].size == 0 and capped == 0
    assert metrics.counter("overlap.join_bailouts") == before + 1


# ------------------------------------------- stage 2.5: ragged streaming

def _stream_rows(hits, starts, counts, parts):
    """``{pair: [6] row}`` from one planned chain stream per part of the
    pairs (``parts``: position arrays into ``starts`` / ``counts``)."""
    rows = {}
    for part in parts:
        st = chain._ChainStream(k=15, tp=hits["tp"], qc=hits["qc"],
                                starts=starts[part], counts=counts[part])
        for idx, block in st.run(chain._plan_chunks(st.counts)):
            assert block.shape == (idx.size, 6)
            assert block.dtype == np.int64
            for pair, row in zip(part[idx].tolist(), block.tolist()):
                assert pair not in rows
                rows[pair] = row
        assert st.launched == part.size and not st.inflight
    return rows


# arena cells, and how the pairs are dealt to streams. 2^19 is the
# program's: every class is one tail chunk. 64 cells: class 16 crosses
# its cap of 4 pairs (full chunks and a tail), classes 64 and up are a
# pair a chunk, each past the in-flight budget on its own
@pytest.mark.parametrize("cells,deal", [
    (1 << 19, 1), (1 << 19, 3), (64, 1), (64, 2), (256, 1)],
    ids=["one-chunk-a-class", "dealt-to-three-streams",
         "class-16-crosses-its-cap", "crossing-and-dealt-to-two",
         "class-64-crosses-its-cap"])
def test_chain_rows_do_not_depend_on_chunk_mates(monkeypatch, cells, deal):
    """A pair's chain row is invariant to which pairs share its chunk:
    whole classes in one chunk, classes cut at their arena cap into
    full chunks and a tail, and the pairs dealt to separate streams all
    give the rows of the phase-barriered ``chain_pairs`` for every
    pair — the property the streamed/barriered byte-identity contract
    rests on."""
    rng = np.random.default_rng(34)
    target = rand_seq(rng, 6000)
    reads = [target[i * 400:i * 400 + 1500] for i in range(8)]
    # short reads: about ten seeds (class 16) and a few tens (class 64)
    reads += [target[i * 300:i * 300 + 40 + 2 * i] for i in range(8)]
    reads += [target[i * 450:i * 450 + 120 + 20 * i] for i in range(6)]
    reads += [revcomp(target[2000:3500]), rand_seq(rng, 900)]
    rt = overlap_seed.build_seed_table(reads)
    tt = overlap_seed.build_seed_table([target])
    self_t = np.full(len(reads), -1, np.int64)
    qlens = np.fromiter((len(r) for r in reads), np.int64, len(reads))
    hits, _ = reference.match_seeds(rt, tt, self_t, qlens, k=15, max_occ=64)
    starts, _, counts = chain._pair_runs(hits)
    classes = chain._seed_buckets(counts)
    assert [chain._seed_bucket(int(c)) for c in counts] == classes.tolist()
    assert len(set(classes.tolist())) >= 3 and starts.size >= 15
    assert (classes == 16).sum() > 4
    # the oracle: whole-bucket chunks at the program's arena
    want, kept, _ = chain.chain_pairs(hits, k=15, min_seeds=1)
    assert kept == starts.size
    monkeypatch.setattr(chain, "CHAIN_ARENA_CELLS", cells)
    pairs = np.arange(starts.size)
    got = _stream_rows(hits, starts, counts,
                       [pairs[i::deal] for i in range(deal)])
    assert sorted(got) == pairs.tolist()
    for pair in pairs:
        assert got[pair] == [int(want[key][pair]) for key in (
            "score", "n_seeds", "q_lo", "q_hi", "t_lo", "t_hi")], pair


def test_chain_intake_is_linear(monkeypatch):
    """6,000 pairs in three seed classes through the plan and the
    stream ``iter_overlap_groups`` uses, launches recorded instead of
    run: the intake visits each pair once (it re-sorted every pending
    pair after every query group: 1,900 visits a pair in
    ``bact2m-auto30x``), every chunk is full but one tail a class,
    chunks leave in the order of their first pair, and no more than
    the in-flight budget is ever unfetched."""
    rng = np.random.default_rng(40)
    n = 6000
    counts = rng.choice([5, 16, 40, 64, 100, 256], n).astype(np.int64)
    starts = np.cumsum(counts) - counts
    monkeypatch.setattr(chain, "CHAIN_ARENA_CELLS", 1 << 14)
    launched, peak = [], []

    def record(self, S, idx):
        B = chain._pair_batch(S)
        launched.append((S, idx))
        out = np.zeros((B, 6), np.int32)
        out[:idx.size, 1] = self.counts[idx]
        self.inflight.append((idx, out, B * S))
        self.inflight_cells += B * S
        self.launched += idx.size
        peak.append(len(self.inflight))

    monkeypatch.setattr(chain._ChainStream, "_launch", record)
    before = metrics.counter("overlap.intake_visits")
    st = chain._ChainStream(k=15, tp=None, qc=None, starts=starts,
                            counts=counts)
    fetched = list(st.run(chain._plan_chunks(counts)))
    visits = metrics.counter("overlap.intake_visits") - before
    assert 0 < visits <= 2 * n

    assert [len(i) for _, i in launched] == [len(i) for i, _ in fetched]
    seen = np.concatenate([idx for idx, _ in fetched])
    assert sorted(seen.tolist()) == list(range(n))
    for idx, rows in fetched:
        assert rows[:, 1].tolist() == counts[idx].tolist()
    firsts = [int(idx[0]) for _, idx in launched]
    assert firsts == sorted(firsts)
    assert max(peak) == chain.CHAIN_INFLIGHT + 1
    for S in (16, 64, 256):
        sizes = [idx.size for s, idx in launched if s == S]
        members = np.concatenate([idx for s, idx in launched if s == S])
        assert (np.diff(members) > 0).all()
        assert set(chain._seed_buckets(counts[members]).tolist()) == {S}
        cap = chain._pair_batch(S)
        tails = [size for size in sizes if size != cap]
        assert len(sizes) >= 2 and len(tails) <= 1, (S, sizes)
        assert all(size < cap for size in tails)


def test_ragged_stream_matches_barrier_rows():
    """find_overlaps emits identical rows (and PAF bytes) across the
    2x2 of {ragged stream, phase barrier} x {device join, host join} —
    the kernel-level half of the acceptance byte-identity matrix; the
    vectorized PAF writer must match its row-at-a-time oracle on the
    same rows."""
    rng = np.random.default_rng(35)
    target = rand_seq(rng, 9000)
    reads = [target[500:3200], revcomp(target[2800:6000]),
             target[5500:8700], rand_seq(rng, 2000),
             revcomp(target[100:1900])]
    self_t = np.full(len(reads), -1, np.int64)
    legs = {}
    for ragged in (True, False):
        for dj in (True, False):
            legs[(ragged, dj)] = chain.find_overlaps(
                reads, [target], self_t, k=15, w=5,
                ragged=ragged, device_join=dj)
    base = legs[(True, True)]
    assert base["q_ord"].size > 0
    for key_leg, rows in legs.items():
        for col in chain._ROW_KEYS:
            assert np.array_equal(rows[col], base[col]), (key_leg, col)
    names = [b"r%d" % i for i in range(len(reads))]
    lens = np.fromiter((len(r) for r in reads), np.int64, len(reads))
    vec = chain.paf_bytes(base, names, lens, [b"t0"],
                          np.array([len(target)], np.int64), k=15)
    oracle = chain.paf_bytes_rowwise(base, names, lens, [b"t0"],
                                     np.array([len(target)], np.int64),
                                     k=15)
    assert vec and vec == oracle
    assert chain.paf_bytes({key: v[:0] for key, v in base.items()},
                           names, lens, [b"t0"],
                           np.array([len(target)], np.int64), k=15) == []


def test_groups_leave_in_order_per_fetched_chunk(monkeypatch):
    """An early query group holds a low-seed (class-16) pair whose
    chunk is a tail: it launches first (chunks go by their first pair),
    so the groups leave in blocks of whole groups, ascending, a block
    per fetch — and the blocks concatenate to the barrier path's rows,
    row for row."""
    rng = np.random.default_rng(37)
    target = rand_seq(rng, 12000)
    reads = [target[100:150]]                      # group 0: ten seeds
    reads += [target[i * 700:i * 700 + 1400] for i in range(14)]
    reads += [revcomp(target[3000:4400]), target[9000:9050],
              rand_seq(rng, 800)]
    self_t = np.full(len(reads), -1, np.int64)
    # a class-1024 chunk is 4 pairs, class 16 one tail of its own
    monkeypatch.setattr(chain, "CHAIN_ARENA_CELLS", 1 << 12)
    before = {name: metrics.counter(name) for name in (
        "overlap.chain_pairs", "overlap.intake_visits")}
    metrics.set_gauge("overlap.first_emit_pairs", 0)
    parts = list(chain.iter_overlap_groups(
        reads, [target], self_t, k=15, w=5, device_join=False))
    pairs, visits = (metrics.counter(name) - was
                     for name, was in before.items())
    assert pairs >= 17 and visits == pairs
    assert 0 < metrics.gauge("overlap.first_emit_pairs") < pairs
    assert len(parts) >= 3
    assert parts[0]["q_ord"][0] == 0
    for a, b in zip(parts, parts[1:]):
        assert a["q_ord"][-1] < b["q_ord"][0]
    want = chain.find_overlaps(reads, [target], self_t, k=15, w=5,
                               ragged=False, device_join=False)
    assert want["q_ord"].size >= 17
    for key in chain._ROW_KEYS:
        assert np.array_equal(np.concatenate([p[key] for p in parts]),
                              want[key]), key


def test_warmed_repeat_run_zero_new_compiles():
    """The serve-job contract: a repeat of an identical overlap run
    dispatches the chain stream into already-compiled executables —
    the jit cache must not grow by a single entry on the second run."""
    rng = np.random.default_rng(36)
    target = rand_seq(rng, 5000)
    reads = [target[200:1800], target[2500:4200],
             revcomp(target[1000:2600])]
    self_t = np.full(3, -1, np.int64)
    first = chain.find_overlaps(reads, [target], self_t, k=15, w=5,
                                ragged=True)
    before = chain._chain_kernel._cache_size()
    again = chain.find_overlaps(reads, [target], self_t, k=15, w=5,
                                ragged=True)
    assert chain._chain_kernel._cache_size() == before
    for col in chain._ROW_KEYS:
        assert np.array_equal(first[col], again[col])


# ------------------------------------------------------------- warm-up

def test_held_read_table_is_keyed_by_k_and_w():
    """The read-side table a caller holds across calls on the same reads
    (a ``--rounds N`` job's) is keyed by what it depends on beside the
    bytes, ``(k, w)``: built once, taken after, and a call at another
    ``k`` builds its own."""
    rng = np.random.default_rng(38)
    target = rand_seq(rng, 6000)
    reads = [target[300:2500], revcomp(target[2000:5200])]
    self_t = np.full(len(reads), -1, np.int64)
    held = {}
    before = [metrics.counter("rounds.read_tables_built"),
              metrics.counter("rounds.read_tables_reused")]
    first = chain.find_overlaps(reads, [target], self_t, k=15, w=5,
                                read_tables=held)
    assert list(held) == [(15, 5)]
    again = chain.find_overlaps(reads, [target], self_t, k=15, w=5,
                                read_tables=held)
    assert list(held) == [(15, 5)]
    assert metrics.counter("rounds.read_tables_built") - before[0] == 1
    assert metrics.counter("rounds.read_tables_reused") - before[1] == 1
    for col in chain._ROW_KEYS:
        assert np.array_equal(first[col], again[col]), col
    assert first["q_ord"].size > 0
    chain.find_overlaps(reads, [target], self_t, k=13, w=5,
                        read_tables=held)
    assert sorted(held) == [(13, 5), (15, 5)]
    assert held[(15, 5)][0].size != held[(13, 5)][0].size or \
        not np.array_equal(held[(15, 5)][0], held[(13, 5)][0])


def test_warmup_shape_cache():
    """warmup_async compiles each (shape, k, w) geometry once per
    process: the first call returns a live thread, an identical second
    call is a cache hit and returns None (the cache-size claim — the
    set grows by exactly the new shapes)."""
    before = len(overlap_seed._warmed_shapes)
    th = overlap_seed.warmup_async(900, 7, k=9, w=4)
    assert th is not None
    th.join(60.0)
    assert not th.is_alive()
    assert len(overlap_seed._warmed_shapes) == before + 1
    assert overlap_seed.warmup_async(900, 7, k=9, w=4) is None
    assert len(overlap_seed._warmed_shapes) == before + 1

    before_c = len(chain._warmed_shapes)
    ladder = chain._warmup_shapes(24, 5)
    assert 1 <= len(ladder) <= 3
    th_c = chain.warmup_async(24, 5, k=9)
    assert th_c is not None
    th_c.join(60.0)
    assert not th_c.is_alive()
    assert len(chain._warmed_shapes) == before_c + len(ladder)
    assert chain.warmup_async(24, 5, k=9) is None
    assert len(chain._warmed_shapes) == before_c + len(ladder)


def test_warmup_zero_estimates_skip():
    assert overlap_seed.warmup_async(0, 0) is None
    assert chain.warmup_async(0, 0) is None


# ------------------------------------------- end-to-end: --overlaps auto

def fasta_bytes(seqs):
    return b"".join(b">" + s.name + b"\n" + s.data + b"\n" for s in seqs)


def auto_single_shot(rp, lp, num_threads=4, type_=PolisherType.C):
    p = create_polisher(str(rp), parsers.AUTO_OVERLAPS, str(lp), type_,
                        num_threads=num_threads)
    return fasta_bytes(p.run(True))


@pytest.fixture(scope="module")
def assembly(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("ovl")
    return write_synthetic_assembly(tmp, seed=41, n_contigs=2,
                                    contig=3000)


def test_auto_mode_polishes(assembly):
    """--overlaps auto end-to-end on the synthetic assembly: both
    contigs polish (the PAF-free path finds the read pile-ups), and the
    output carries the standard polished headers."""
    rp, _, lp = assembly
    out = auto_single_shot(rp, lp)
    assert out.count(b">") == 2
    assert b"ctg0" in out and b"ctg1" in out


def test_auto_mode_thread_determinism(assembly):
    """Auto-mode output is byte-identical across worker thread counts
    (the overlapper sorts canonically; threading must not leak in)."""
    rp, _, lp = assembly
    assert auto_single_shot(rp, lp, num_threads=1) == \
        auto_single_shot(rp, lp, num_threads=4)


def test_auto_mode_shards_byte_identical(assembly, tmp_path):
    """A --shards 2 auto run (PAF materialized into the work dir, index
    replayed over it) is byte-identical to the single-shot in-memory
    path — the acceptance determinism contract."""
    rp, _, lp = assembly
    want = auto_single_shot(rp, lp)
    runner = ShardRunner(str(rp), parsers.AUTO_OVERLAPS, str(lp),
                         work_dir=str(tmp_path / "work"), n_shards=2,
                         num_threads=4)
    buf = io.BytesIO()
    summary = runner.run(buf)
    assert buf.getvalue() == want
    assert summary["n_shards"] == 2
    assert (tmp_path / "work" / "auto_overlaps.paf").stat().st_size > 0


def test_auto_mode_flag_matrix_byte_identical(assembly, tmp_path,
                                              monkeypatch):
    """The acceptance determinism matrix at the polisher level: the
    polished FASTA is byte-identical across {device join, host join} x
    {streaming handoff, barrier} — including a barriered --shards 2 run
    against the default streamed single-shot."""
    rp, _, lp = assembly
    want = auto_single_shot(rp, lp)
    for dj, rag in (("0", "1"), ("1", "0"), ("0", "0")):
        monkeypatch.setenv("RACON_TPU_OVERLAP_DEVICE_JOIN", dj)
        monkeypatch.setenv("RACON_TPU_OVERLAP_RAGGED", rag)
        assert auto_single_shot(rp, lp) == want, (dj, rag)
    runner = ShardRunner(str(rp), parsers.AUTO_OVERLAPS, str(lp),
                         work_dir=str(tmp_path / "work"), n_shards=2,
                         num_threads=4)
    buf = io.BytesIO()
    runner.run(buf)
    assert buf.getvalue() == want


def test_auto_mode_f_mode(assembly):
    """Fragment correction (-f) with auto overlaps: reads map against
    the read pool itself with self-hits suppressed, and correction
    emits corrected reads."""
    rp, _, _ = assembly
    out = auto_single_shot(rp, rp, type_=PolisherType.F)
    assert out.count(b">") > 10


def test_auto_paf_input_variants(assembly, tmp_path):
    """write_auto_paf emits identical PAF bytes whether the reads
    arrive as FASTQ, gzipped FASTQ, or FASTA — parser-layer variance
    must not reach the overlapper."""
    rp, _, lp = assembly
    raw = pathlib.Path(rp).read_bytes()
    gz = tmp_path / "reads.fastq.gz"
    with gzip.open(gz, "wb") as f:
        f.write(raw)
    fa = tmp_path / "reads.fasta"
    lines = raw.split(b"\n")
    with open(fa, "wb") as f:
        for i in range(0, len(lines) - 3, 4):
            f.write(b">" + lines[i][1:] + b"\n" + lines[i + 1] + b"\n")
    outs = []
    for i, reads in enumerate((rp, gz, fa)):
        paf = tmp_path / f"auto{i}.paf"
        write_auto_paf(str(reads), str(lp), str(paf))
        outs.append(paf.read_bytes())
    assert outs[0] and outs[0] == outs[1] == outs[2]


def test_auto_mode_rejects_bad_extension_still(assembly):
    """'auto' is a sentinel, not a loosened parser: a real path with an
    unknown extension still raises."""
    rp, _, lp = assembly
    with pytest.raises(ValueError, match="auto"):
        create_polisher(str(rp), "overlaps.xyz", str(lp),
                        PolisherType.C, num_threads=1)


# ----------------------------------------- planner / rampler auto cases

def test_estimate_job_cost_auto(assembly):
    """Auto jobs have no overlaps file: the estimate charges the reads
    term once more instead, and never trips on a missing path."""
    rp, pp, lp = assembly
    auto = estimate_job_cost(str(rp), parsers.AUTO_OVERLAPS, str(lp))
    paf = estimate_job_cost(str(rp), str(pp), str(lp))
    assert auto > 0 and paf > 0


def test_rampler_plan_auto(assembly):
    """rampler plan with --overlaps auto: a reads-only index (reads
    apportioned to contigs by size) feeds the planner without a PAF."""
    from racon_tpu import rampler
    rp, _, lp = assembly
    out = rampler.plan(str(rp), parsers.AUTO_OVERLAPS, str(lp),
                       n_shards=2)
    assert out["n_contigs"] == 2 and out["n_overlaps"] == 0
    assert len(out["shards"]) == 2
    assert all(s["contigs"] for s in out["shards"])


def test_readsonly_index_apportions_reads(assembly):
    rp, _, lp = assembly
    idx = build_index_readsonly(str(rp), str(lp))
    assert idx.uniform_read_bases > 0
    per_contig = idx.contig_read_bytes()
    assert per_contig.size == len(idx.targets)
    assert all(int(b) > 0 for b in per_contig)
    assert int(per_contig.sum()) <= idx.uniform_read_bases
