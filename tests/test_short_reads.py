"""Short accurate reads on the path the benchmark's cells take: one
device, the ragged streams (``_AlignStream``, ``_ConsensusStream``).

The deployment is ``illumina-contig-default`` (``benchmark/configs/``):
150-base reads at a depth over the consensus packer's 200-layer cap,
polishing a long-read draft — the cell ``bact-sr150-50x`` at test
size. Held here: the window type the read set decides (NGS, no end
trim), the aligner's smallest bucket against the plain reference
``models/nw.py`` pair for pair, which layers of an over-deep window
vote, the counters a run report carries for all of it, and the whole
job's FASTA against the plain reference's (``models/nw.py`` +
``models/poa.py``).

``tests/conftest.py`` gives every test eight virtual devices, which
send ``cli.main`` down the mesh path; the jobs here are steered to one
device in the test (``backends._auto_mesh``), not through an option of
the program (ROADMAP D12).
"""

import json
import os
import sys

import numpy as np
import pytest

from racon_tpu import cli, native
from racon_tpu.core import backends
from racon_tpu.core.overlap import (bp_array_to_pairs,
                                    breaking_points_from_cigar)
from racon_tpu.core.polisher import create_polisher
from racon_tpu.core.window import Window, WindowType
from racon_tpu.models.nw import edit_distance, nw_align
from racon_tpu.obs import metrics, trace
from racon_tpu.ops import nw, poa
from tests.test_consensus_lanes import _Stdout
from tests.test_ngs import _write_set

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASES = np.frombuffer(b"ACGT", np.uint8)
# the cell's flags (benchmark/configs/illumina-contig-default.json)
FLAGS = ["-w", "500", "-q", "10", "-e", "0.3", "-m", "3", "-x", "-5",
         "-g", "-4"]


def _cell_traffic(contig: int, coverage: int) -> dict:
    """The cell's own traffic file, cut to ``contig`` bases."""
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "one-contig-sr150-50x.json")) as fh:
        traffic = json.load(fh)
    return {**traffic, "contig_sizes": [contig], "coverage": coverage}


def _simulate(traffic: dict, seed: int, out_dir: str) -> dict:
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    try:
        from harness.simulate import write_inputs
    finally:
        sys.path.remove(os.path.join(ROOT, "benchmark"))
    return write_inputs(traffic, seed, out_dir)


def _contig(fasta: bytes) -> bytes:
    return b"".join(fasta.split(b"\n")[1:])


# ------------------------------------------------ the heuristic's edge

@pytest.mark.parametrize("read_len,want", [
    (150, WindowType.NGS), (400, WindowType.NGS), (1000, WindowType.NGS),
    (1001, WindowType.TGS)])
def test_mean_read_length_decides_the_window_type(tmp_path, read_len,
                                                  want):
    """``src/polisher.cpp:275-276``: mean read length <= 1000 is NGS.
    The one rule (``WindowType.of_reads``) as the polisher applies it,
    and as the run report states it (gauge ``polisher.window_type``)."""
    assert WindowType.of_reads(read_len * 40, 40) is want
    reads, paf, layout = _write_set(tmp_path, read_len, contig_len=3000)
    p = create_polisher(str(reads), str(paf), str(layout), num_threads=2)
    p.initialize()
    assert p.windows and all(w.type is want for w in p.windows)
    assert metrics.gauge("polisher.window_type", None) == want.value


# ---------------------------------------- the aligner's smallest bucket

def _short_pairs(rng, count: int):
    """Pairs as the cell forms them: a 100-150 base read against its
    span of a 99.5 % draft — and, every fourth pair, a read cut at a
    contig's end to 20-60 bases."""
    pairs = []
    for k in range(count):
        ln = int(rng.integers(20, 61) if k % 4 == 3
                 else rng.integers(100, 151))
        t = BASES[rng.integers(0, 4, ln)]
        q = t.copy()
        sub = rng.random(ln) < 0.004
        q[sub] = BASES[rng.integers(0, 4, int(sub.sum()))]
        q = np.delete(q, np.flatnonzero(rng.random(ln) < 0.004))
        if k % 5 == 0:
            at = int(rng.integers(1, len(q)))
            q = np.concatenate([q[:at], BASES[rng.integers(0, 4, 1)],
                                q[at:]])
        pairs.append((q.tobytes(), t.tobytes()))
    return pairs


@pytest.fixture(scope="module")
def bucket_run():
    rng = np.random.default_rng(37)
    pairs = _short_pairs(rng, 1200)
    metas = [(int(rng.integers(0, 100_000)), int(rng.integers(0, 3)))
             for _ in pairs]
    eng = nw.TpuAligner(fallback=backends.PythonAligner())
    metrics.clear_run()
    sess = eng.bp_stream(500, total=len(pairs))
    sess.feed(pairs, metas, [0.0] * len(pairs))
    bps = sess.finish()
    counters = metrics.snapshot()["counters"]
    return pairs, metas, eng, bps, eng.align_batch(pairs), counters


def test_short_pairs_take_the_smallest_bucket_on_the_stream(bucket_run):
    pairs, _, eng, _, _, counters = bucket_run
    assert nw.BUCKETS[0] == (256, 128)
    by_bucket = {k: v for k, v in counters.items()
                 if k.startswith("align.pairs_by_bucket.")}
    # the probe's chunk and the rest, every pair once, none escaped
    assert by_bucket == {"align.pairs_by_bucket.256": len(pairs)}
    assert eng.stats["fallback_length"] == eng.stats["fallback_band"] == 0
    assert eng.stats["band_escalated"] == 0
    assert counters["align.chunks"] >= 2
    # after the probe the ladder seeds under the bucket's band
    assert counters["aligner.ladder_narrow"] > 0


def test_smallest_bucket_equals_the_plain_reference_pair_for_pair(
        bucket_run):
    """Device CIGARs against ``models/nw.py``: the same optimum for
    every pair (the reference's own CIGAR where the optimum is unique
    in cost and shape), and the streamed breaking points equal to a
    walk of the device CIGAR, 20-60 base pairs included."""
    pairs, metas, _, bps, cigars, _ = bucket_run
    same = 0
    for (q, t), (t_begin, q_off), bp, cig in zip(pairs, metas, bps,
                                                 cigars):
        ref = nw_align(q, t)
        same += cig == ref
        assert _cost(cig, q, t) == _cost(ref, q, t) == edit_distance(q, t)
        oracle = breaking_points_from_cigar(cig, q_off, t_begin,
                                            t_begin + len(t), 500)
        assert bp_array_to_pairs(bp) == oracle
    assert same >= 0.9 * len(pairs), same


def test_narrow_bands_never_take_the_packed_mosaic_kernel(monkeypatch):
    """On the chip the packed Mosaic forward kernel is wrong under a
    band of 512 wherever a pair block has more than 8 rows (PR 37: at
    (256, 128 / 96 / 64) it scored every substitution 0). No CPU run
    executes a Mosaic kernel, so what is held here is the engine's
    decision: with the Mosaic family on, a chunk of the smallest bucket
    dispatches the int32 kernel and a long-read chunk the packed one."""
    from racon_tpu.ops import pallas_nw, swar
    assert [swar.mosaic_swar_fits(b) for b in (64, 96, 128, 256, 384)] \
        == [False] * 5
    assert all(swar.mosaic_swar_fits(b) for b in (512, 768, 1024, 4096))
    monkeypatch.setattr(pallas_nw, "pallas_swar_ok", lambda: True)
    monkeypatch.setattr(swar, "swar_ok", lambda: True)
    eng = nw.TpuAligner(fallback=backends.PythonAligner())
    monkeypatch.setattr(eng, "_use_pallas", lambda key: True)
    dispatch, seen = eng._dispatch, []

    def recording(args, max_len, band, steps, use_pallas, use_swar=False):
        seen.append((max_len, band, use_pallas, use_swar))
        return dispatch(args, max_len, band, steps, False, use_swar)
    monkeypatch.setattr(eng, "_dispatch", recording)
    rng = np.random.default_rng(3)
    short = _short_pairs(rng, 40)
    t = BASES[rng.integers(0, 4, 3000)]
    q = t.copy()
    q[rng.random(3000) < 0.1] = ord("A")
    long_pairs = [(q.tobytes(), t.tobytes())] * 3
    cigars = eng.align_batch(short + long_pairs)
    assert all(cigars)
    assert seen and all(use_pallas for _, _, use_pallas, _ in seen)
    by_bucket = {(max_len, band): sw for max_len, band, _, sw in seen}
    assert {sw for (ml, _), sw in by_bucket.items() if ml == 256} == {False}
    assert {sw for (ml, band), sw in by_bucket.items()
            if band >= 512} == {True}


def _cost(cigar: str, q: bytes, t: bytes) -> int:
    from tests.test_nw import cigar_consumes, cigar_cost
    assert cigar_consumes(cigar) == (len(q), len(t))
    return cigar_cost(cigar, q, t)


# ------------------------------------- the host pack, without its loops

def _pack_blocks_by_loops(eng, pairs, chunk, max_len, bp_meta, sw):
    """``TpuAligner._pack_blocks`` as it was written until PR 37: a
    Python step per pair, twice. The reference the vectorised pack is
    held to, array for array."""
    from racon_tpu.ops.swar import pack_bases_2bit
    B = eng._pad_batch(len(chunk))
    qcat = np.zeros(B * max_len, dtype=np.uint8)
    tcat = np.zeros(B * max_len, dtype=np.uint8)
    n, m = np.ones(B, dtype=np.int32), np.ones(B, dtype=np.int32)
    for k, idx in enumerate(chunk):
        qb, tb = pairs[idx]
        qcat[k * max_len: k * max_len + len(qb)] = np.frombuffer(qb, np.uint8)
        tcat[k * max_len: k * max_len + len(tb)] = np.frombuffer(tb, np.uint8)
        n[k], m[k] = len(qb), len(tb)
    hist = np.bincount(qcat, minlength=256) + np.bincount(tcat, minlength=256)
    alphabet = np.flatnonzero(hist[1:]) + 1
    lut = np.zeros(256, np.uint8)
    if sw and len(alphabet) <= 4:
        lut[alphabet] = np.arange(len(alphabet), dtype=np.uint8)
        kind, seqs = "2bit", (pack_bases_2bit(lut[qcat]),
                              pack_bases_2bit(lut[tcat]))
    elif len(alphabet) <= 15:
        lut[alphabet] = np.arange(1, len(alphabet) + 1, dtype=np.uint8)
        q4, t4 = lut[qcat], lut[tcat]
        kind, seqs = "nibble", (q4[0::2] | (q4[1::2] << 4),
                                t4[0::2] | (t4[1::2] << 4))
    else:
        kind, seqs = "raw", (qcat, tcat)
    w, metas = bp_meta
    first_rel, nb = np.zeros(B, np.int32), np.ones(B, np.int32)
    for k, idx in enumerate(chunk):
        t_begin, _ = metas[idx]
        t_end = t_begin + len(pairs[idx][1])
        n_reg = (t_end - 1) // w - t_begin // w
        nb[k] = n_reg + 1
        first_rel[k] = ((t_begin // w + 1) * w - 1 - t_begin
                        if n_reg else m[k] - 1)
    return n, m, seqs, kind, (first_rel, nb)


@pytest.mark.parametrize("core", ["native", "numpy"])
@pytest.mark.parametrize("sw", [True, False])
@pytest.mark.parametrize("alphabet,kind", [
    (b"ACGT", "2bit"), (b"ACG", "2bit"), (b"T", "2bit"),
    (b"ACGTN", "nibble"), (b"ACGTacgtNRYKM", "nibble"),
    (bytes(range(1, 40)), "raw")])
def test_pack_blocks_equal_a_loop_per_pair(monkeypatch, core, sw,
                                           alphabet, kind):
    """The chunk's blocks written by row copies after one
    ``bytes.translate`` over the spans (65,536 short pairs a chunk in
    the cell) are the arrays the loop per pair wrote, in every packing
    and for every window geometry, with the native copier and without."""
    if core == "numpy":
        monkeypatch.setattr(native, "available", lambda: False)
    elif not native.available():
        pytest.skip("no native core here")
    rng = np.random.default_rng(len(alphabet) + sw)
    symbols = np.frombuffer(alphabet, np.uint8)
    pairs, metas = {}, {}
    for slot in range(70):
        pairs[slot] = tuple(
            symbols[rng.integers(0, len(symbols),
                                 int(rng.integers(1, 257)))].tobytes()
            for _ in range(2))
        metas[slot] = (int(rng.integers(0, 100_000)),
                       int(rng.integers(0, 40)))
    chunk = rng.permutation(70)[:61].tolist()
    eng = nw.TpuAligner(fallback=None)
    for w in (500, 64):
        got = eng._pack_blocks(pairs, chunk, 256, (w, metas), sw)
        want = _pack_blocks_by_loops(eng, pairs, chunk, 256, (w, metas),
                                     sw)
        assert got[3] == want[3] == (kind if sw or kind != "2bit"
                                     else "nibble")
        for a, b in zip((got[0], got[1], *got[2], *got[4]),
                        (want[0], want[1], *want[2], *want[4])):
            assert a.dtype == b.dtype and np.array_equal(a, b)


def test_byte_row_copier_refuses_rows_outside_the_block():
    if not native.available():
        pytest.skip("no native core here")
    block = np.zeros((4, 8), np.uint8)
    native.copy_byte_rows(b"abcdefghij", [3, 0, 7], block)
    assert block.tobytes() == (b"abc" + bytes(5) + bytes(8)
                               + b"defghij" + bytes(1) + bytes(8))
    for pool, lens in ((b"abc", [2, 2]), (b"abcdefghi", [9]),
                       (b"abcde", [1, 1, 1, 1, 1])):
        with pytest.raises(IndexError):
            native.copy_byte_rows(pool, lens, block)
    with pytest.raises(ValueError):
        native.copy_byte_rows(b"ab", [2], np.zeros((4, 8), np.uint16))


def test_read_records_built_in_c_equal_the_ctypes_route(tmp_path,
                                                        monkeypatch):
    """``native/pyext.cpp`` ``parse_seqfile`` builds the record tuples
    the ctypes route builds (345,000 of them a draft Mbp here)."""
    if native.load_ext() is None:
        pytest.skip("no CPython extension here")
    fq = tmp_path / "r.fastq"
    fq.write_bytes(b"@r1 x\nACGT\n+\nFFFF\n@r2\nAC\nGT\n+r2\nFF\nFF\n")
    fa = tmp_path / "r.fasta"
    fa.write_bytes(b">c1 y\nACGT\nAC\n>c2\nT\n")
    by_ext = [native.parse_seqfile(str(fq), True),
              native.parse_seqfile(str(fa), False)]
    monkeypatch.setattr(native, "load_ext", lambda: None)
    assert by_ext == [native.parse_seqfile(str(fq), True),
                      native.parse_seqfile(str(fa), False)]
    assert by_ext[0] == [(b"r1", b"ACGT", b"FFFF"),
                         (b"r2", b"ACGT", b"FFFF")]
    assert by_ext[1] == [(b"c1", b"ACGTAC", None), (b"c2", b"T", None)]
    with pytest.raises(ValueError):
        monkeypatch.undo()
        native.parse_seqfile(str(tmp_path / "absent.fastq"), True)


@pytest.mark.parametrize("length", [1, 150, 1023, 1024, 5000])
def test_reverse_complement_is_one_function_of_the_bytes(length):
    """Short reads take ``bytes.translate``, long ones the numpy LUT:
    the same bytes either way, qualities reversed, non-ACGT kept."""
    from racon_tpu.core.sequence import Sequence
    rng = np.random.default_rng(length)
    data = np.frombuffer(b"ACGTNacgtRY", np.uint8)[
        rng.integers(0, 11, length)].tobytes()
    qual = bytes(rng.integers(34, 74, length).tolist())
    seq = Sequence(b"r", data, qual)
    want = data.upper().translate(
        bytes.maketrans(b"ACGT", b"TGCA"))[::-1]
    assert seq.reverse_complement == want
    assert seq.reverse_quality == qual[::-1]
    assert Sequence(b"r", data, b"!" * length).quality is None


# ------------------------------------------------------- the depth cap

def _deep_window(rng, depth: int) -> Window:
    backbone = BASES[rng.integers(0, 4, 500)]
    win = Window(0, 0, WindowType.NGS, backbone.tobytes(), b"!" * 500)
    for _ in range(depth):
        a = int(rng.integers(0, 351))
        win.add_layer(backbone[a:a + 150].tobytes(), b"F" * 150, a,
                      a + 149)
    return win


def test_the_first_200_layers_by_arrival_vote():
    """A 260-layer window keeps its first 200 layers in arrival
    (overlap-stream) order; the 60 that came last do not vote. The
    three counters say so, and a window under the cap writes zeros."""
    rng = np.random.default_rng(5)
    eng = poa.TpuPoaConsensus(3, -5, -4, fallback=None)
    assert eng.max_depth == 200
    deep, shallow = _deep_window(rng, 260), _deep_window(rng, 40)
    metrics.clear_run()
    work = poa._Work(deep, eng.max_depth, eng.stats)
    assert work.n_layers == 200 and work.n_seqs == 261
    assert [seq for seq, _, _, _ in work.layers] == deep.sequences[1:201]
    assert work.begins.tolist() == [b for b, _ in deep.positions[1:201]]
    assert metrics.counter("consensus.windows") == 1
    assert metrics.counter("consensus.layers") == 260
    assert metrics.counter("consensus.dropped_layers") == 60
    assert metrics.counter("consensus.windows_capped") == 1
    poa._Work(shallow, eng.max_depth, eng.stats)
    assert metrics.counter("consensus.layers") == 300
    assert metrics.counter("consensus.dropped_layers") == 60
    assert metrics.counter("consensus.windows_capped") == 1
    metrics.clear_run()
    poa._Work(shallow, eng.max_depth, eng.stats)
    counters = metrics.snapshot()["counters"]
    assert counters["consensus.dropped_layers"] == 0
    assert counters["consensus.windows_capped"] == 0


# ------------------------------------------------------- the whole job

# 1,480 bases: the draft (0.2 % shorter or longer) stays in three windows
CONTIG, COVERAGE, SEED = 1480, 60, 2**31 + 37


@pytest.fixture(scope="module")
def job(tmp_path_factory):
    """The cell's traffic at 1,480 bases and 60x (more than 200 layers
    in each of the three windows) through ``cli.main`` on one device,
    and the same inputs through the plain reference engines."""
    td = tmp_path_factory.mktemp("short_reads")
    paths = _simulate(_cell_traffic(CONTIG, COVERAGE), SEED, str(td))
    inputs = [paths["reads"], paths["overlaps"], paths["draft"]]
    rep = td / "report.json"
    auto_mesh = backends._auto_mesh
    run_padded = poa.TpuPoaConsensus._run_padded
    padded_runs = []

    def counted(self, *args, **kwargs):
        padded_runs.append(1)
        return run_padded(self, *args, **kwargs)
    try:
        trace.deactivate()
        backends._auto_mesh = lambda mesh: mesh
        poa.TpuPoaConsensus._run_padded = counted
        with _Stdout() as captured:
            rc = cli.main(["-t", "2", "-c", "1", "--tpualigner-batches",
                           "1", *FLAGS, "--run-report", str(rep),
                           *inputs])
    finally:
        backends._auto_mesh = auto_mesh
        poa.TpuPoaConsensus._run_padded = run_padded
        trace.deactivate()
    assert rc == 0
    plain = create_polisher(*inputs, num_threads=2,
                            aligner_backend="python",
                            consensus_backend="python")
    plain.initialize()
    depths = [w.layer_count for w in plain.windows]
    (polished,) = plain.polish(True)
    with open(paths["truth"], "rb") as fh:
        truth = _contig(fh.read())
    with open(paths["draft"], "rb") as fh:
        draft = _contig(fh.read())
    return {"fasta": _contig(captured.bytes), "plain": polished.data,
            "report": json.loads(rep.read_bytes()), "depths": depths,
            "padded_runs": len(padded_runs), "truth": truth,
            "draft": draft,
            "pairs": sum(1 for _ in open(paths["overlaps"], "rb"))}


def test_job_ran_the_streams_as_ngs_in_the_smallest_bucket(job):
    m = job["report"]["metrics"]
    assert job["padded_runs"] == 0
    assert m["gauges"]["polisher.window_type"] == WindowType.NGS.value
    by_bucket = {k: v for k, v in m["counters"].items()
                 if k.startswith("align.pairs_by_bucket.")}
    assert set(by_bucket) == {"align.pairs_by_bucket.256"}
    assert by_bucket["align.pairs_by_bucket.256"] >= job["pairs"]
    assert m["counters"]["consensus.groups"] >= 1
    assert m["counters"].get("consensus.fallback_windows", 0) == 0
    assert not job["report"]["swallowed"]


def test_job_counts_the_depth_cap_as_a_hand_count_does(job):
    """Every window of the job is over the cap; what the run report
    counts is what the plain reference's windows hold."""
    depths, c = job["depths"], job["report"]["metrics"]["counters"]
    assert len(depths) == 3 and min(depths) > 200
    assert c["consensus.layers"] == sum(depths)
    assert c["consensus.windows"] == c["consensus.windows_capped"] == 3
    assert c["consensus.dropped_layers"] == sum(d - 200 for d in depths)
    assert c["consensus.group_windows"] == 3
    # the lane block is a twelfth full: 150-base rows in Lq 1,024
    assert 0.05 < c["consensus.lanes_occupied"] \
        / c["consensus.lanes_total"] < 0.15


def test_job_equals_the_plain_reference_within_five_edits(job):
    """The device path keeps 200 layers a window and votes by pile-up;
    the plain reference (``models/nw.py`` + ``models/poa.py``) aligns
    every layer to a graph. From 50x of 99.7 % reads both should leave
    a near-perfect contig: each within 5 edits of the truth and of each
    other, from a draft 5 or more away — and no end trim (NGS): the
    contig keeps the truth's length within those edits."""
    d_draft = native.edit_distance(job["draft"], job["truth"])
    d_device = native.edit_distance(job["fasta"], job["truth"])
    d_plain = native.edit_distance(job["plain"], job["truth"])
    assert d_draft >= 5
    assert d_device <= 5 and d_plain <= 5, (d_device, d_plain, d_draft)
    assert native.edit_distance(job["fasta"], job["plain"]) <= 5
    assert abs(len(job["fasta"]) - len(job["truth"])) <= 5
