"""Parser tests on the reference's λ-phage dataset (read in place from
/root/reference/test/data — public test fixtures, not code)."""

from racon_tpu.io import (
    parse_fasta, parse_fastq, parse_paf, parse_mhap, parse_sam,
    sequence_parser_for, overlap_parser_for,
)


def test_fasta_layout(data_dir):
    recs = list(parse_fasta(str(data_dir / "sample_layout.fasta.gz")))
    assert len(recs) == 1
    assert recs[0].name == b"utg000001l"
    assert len(recs[0].data) == 47564
    assert recs[0].quality is None


def test_fastq_reads_multiline(data_dir):
    recs = list(parse_fastq(str(data_dir / "sample_reads.fastq.gz")))
    assert len(recs) > 100
    for r in recs:
        assert len(r.data) == len(r.quality)
    total = sum(len(r.data) for r in recs)
    assert total > 1_000_000  # ~1.6 Mbp of ONT reads


def test_paf(data_dir):
    recs = list(parse_paf(str(data_dir / "sample_overlaps.paf.gz")))
    assert len(recs) == 181
    qn, ql, qb, qe, strand, tn, tl, tb, te = recs[0].fields
    assert tn == b"utg000001l" and tl == 47564
    assert strand in "+-"
    assert 0 <= qb < qe <= ql


def test_mhap(data_dir):
    recs = list(parse_mhap(str(data_dir / "sample_ava_overlaps.mhap.gz")))
    assert len(recs) > 1000
    a_id, b_id, _, _, a_rc, ab, ae, al, b_rc, bb, be, bl = recs[0].fields
    assert a_id >= 1 and b_id >= 1
    assert a_rc in (0, 1) and b_rc in (0, 1)


def test_sam(data_dir):
    recs = list(parse_sam(str(data_dir / "sample_overlaps.sam.gz")))
    assert len(recs) > 100
    qn, flag, tn, pos, cigar = recs[0].fields
    assert tn == b"utg000001l"
    assert pos >= 1
    assert any(c in b"MIDSH=X" for c in cigar)


def test_dispatch():
    assert sequence_parser_for("x.fasta.gz") is parse_fasta
    assert sequence_parser_for("x.fq") is parse_fastq
    assert sequence_parser_for("x.bam") is None
    assert overlap_parser_for("x.paf.gz") is parse_paf
    assert overlap_parser_for("x.mhap") is parse_mhap
    assert overlap_parser_for("x.sam.gz") is parse_sam
    assert overlap_parser_for("x.vcf") is None


def test_native_parser_matches_python_oracle(data_dir):
    """The native zlib parser must produce record-for-record identical
    output to the Python parsers on the real λ files (gzipped FASTA and
    FASTQ, multi-record, names with suffixes)."""
    import racon_tpu.io.parsers as P
    from racon_tpu import native

    if not native.available():
        import pytest
        pytest.skip("native library unavailable")

    for fname, is_fastq in (("sample_reads.fasta.gz", False),
                            ("sample_reads.fastq.gz", True),
                            ("sample_layout.fasta.gz", False)):
        path = str(data_dir / fname)
        got = native.parse_seqfile(path, is_fastq)
        # bypass the native fast path to reach the Python oracle
        import unittest.mock as mock
        with mock.patch.object(P, "_native_records", lambda *a: None):
            want = list((P.parse_fastq if is_fastq else P.parse_fasta)(path))
        assert len(got) == len(want)
        for g, w in zip(got, want):
            assert g[0] == w.name and g[1] == w.data and g[2] == w.quality


def test_native_parser_rejects_malformed(tmp_path):
    from racon_tpu import native

    if not native.available():
        import pytest
        pytest.skip("native library unavailable")
    bad = tmp_path / "bad.fastq"
    bad.write_bytes(b"not a header\nACGT\n+\n!!!!\n")
    import pytest
    with pytest.raises(ValueError, match="malformed FASTQ header"):
        native.parse_seqfile(str(bad), True)
    trunc = tmp_path / "trunc.fastq"
    trunc.write_bytes(b"@r1\nACGTACGT\n+\n!!!\n")
    with pytest.raises(ValueError, match="truncated FASTQ"):
        native.parse_seqfile(str(trunc), True)


def test_native_parser_skips_leading_header_whitespace(tmp_path):
    """'>  name extra' must yield b'name' like the Python oracle's
    split(None, 1)."""
    from racon_tpu import native

    if not native.available():
        import pytest
        pytest.skip("native library unavailable")
    f = tmp_path / "pad.fasta"
    f.write_bytes(b">  ctg1 extra\nACGT\n")
    (rec,) = native.parse_seqfile(str(f), False)
    assert rec[0] == b"ctg1" and rec[1] == b"ACGT"


def test_native_ovl_parser_matches_python_oracle(data_dir):
    """The native overlap parser (PAF/MHAP/SAM) must produce field
    tuples identical to the Python oracle parsers on the real λ files,
    including the float jaccard (both are correctly-rounded doubles of
    the same token) and the SAM header skip."""
    import racon_tpu.io.parsers as P
    from racon_tpu import native

    if not native.available():
        import pytest
        pytest.skip("native library unavailable")

    import unittest.mock as mock
    for fname, fmt, parser in (
            ("sample_overlaps.paf.gz", 0, P.parse_paf),
            ("sample_ava_overlaps.paf.gz", 0, P.parse_paf),
            ("sample_ava_overlaps.mhap.gz", 1, P.parse_mhap),
            ("sample_overlaps.sam.gz", 2, P.parse_sam)):
        path = str(data_dir / fname)
        got = native.parse_ovlfile(path, fmt)
        with mock.patch.object(P, "_native_ovl", lambda *a: None):
            want = list(parser(path))
        assert len(got) == len(want)
        assert [r.fields for r in got] == [r.fields for r in want]
        assert all(g.fmt == w.fmt for g, w in zip(got, want))


def test_native_ovl_parser_rejects_malformed(tmp_path):
    from racon_tpu import native

    if not native.available():
        import pytest
        pytest.skip("native library unavailable")
    bad = tmp_path / "bad.paf"
    bad.write_bytes(b"q1\t100\t0\t100\n")  # too few fields
    import pytest
    with pytest.raises(ValueError, match="malformed line 1"):
        native.parse_ovlfile(str(bad), 0)


def test_ctypes_ovl_fallback_matches_oracle(data_dir):
    """The ctypes record-reconstruction path (used when the CPython
    extension cannot build) must match the oracle too."""
    import unittest.mock as mock
    import racon_tpu.io.parsers as P
    from racon_tpu import native

    if not native.available():
        import pytest
        pytest.skip("native library unavailable")

    with mock.patch.object(native, "load_ext", lambda: None):
        for fname, fmt, parser in (
                ("sample_overlaps.paf.gz", 0, P.parse_paf),
                ("sample_ava_overlaps.mhap.gz", 1, P.parse_mhap),
                ("sample_overlaps.sam.gz", 2, P.parse_sam)):
            path = str(data_dir / fname)
            got = native.parse_ovlfile(path, fmt)
            with mock.patch.object(P, "_native_ovl", lambda *a: None):
                want = list(parser(path))
            assert [r.fields for r in got] == [r.fields for r in want]
            assert all(g.fmt == w.fmt for g, w in zip(got, want))


# ----------------------------------------------- structured parse errors

def test_parse_error_carries_file_and_line(tmp_path):
    """Malformed records surface as ParseError (a ValueError) with the
    file and the 1-based line number in the message — the round-12
    parser-hardening satellite. The Python oracles are exercised
    directly so the line numbers are deterministic regardless of the
    native build."""
    import pytest

    import racon_tpu.io.parsers as P

    fq = tmp_path / "bad.fastq"
    fq.write_bytes(b"@r1\nACGT\n+\n!!!!\nnot a header\nACGT\n+\n!!!!\n")
    with pytest.raises(P.ParseError, match=r"bad\.fastq:5.*malformed "
                                           r"FASTQ header") as ei:
        list(P._parse_fastq_py(str(fq)))
    assert ei.value.line == 5 and ei.value.path == str(fq)

    trunc = tmp_path / "trunc.fastq"
    trunc.write_bytes(b"@r1\nACGTACGT\n+\n!!!\n")
    with pytest.raises(P.ParseError, match=r"trunc\.fastq:1.*truncated"):
        list(P._parse_fastq_py(str(trunc)))

    nosep = tmp_path / "nosep.fastq"
    nosep.write_bytes(b"@r1\nACGT\nACGT\n")
    with pytest.raises(P.ParseError, match=r"no '\+' separator"):
        list(P._parse_fastq_py(str(nosep)))

    fa = tmp_path / "headerless.fasta"
    fa.write_bytes(b"ACGTACGT\n>ctg\nACGT\n")
    with pytest.raises(P.ParseError, match=r"headerless\.fasta:1.*"
                                           r"before the first"):
        list(P._parse_fasta_py(str(fa)))

    noname = tmp_path / "noname.fasta"
    noname.write_bytes(b">\nACGT\n")
    with pytest.raises(P.ParseError, match=r"noname\.fasta:1.*empty "
                                           r"sequence name"):
        list(P._parse_fasta_py(str(noname)))


def test_overlap_parse_errors_carry_file_and_line(tmp_path):
    import pytest

    import racon_tpu.io.parsers as P

    paf = tmp_path / "bad.paf"
    paf.write_bytes(b"q1\t100\t0\t100\t+\tt1\t100\t0\t100\t50\t100\t255\n"
                    b"q2\t100\t0\n")
    with pytest.raises(P.ParseError, match=r"bad\.paf:2.*malformed PAF"):
        list(P._parse_paf_py(str(paf)))
    notint = tmp_path / "notint.paf"
    notint.write_bytes(b"q1\tNaN\t0\t100\t+\tt1\t100\t0\t100\t5\t10\t2\n")
    with pytest.raises(P.ParseError, match=r"notint\.paf:1"):
        list(P._parse_paf_py(str(notint)))

    mhap = tmp_path / "bad.mhap"
    mhap.write_bytes(b"1 2 0.1 5 0 0 100 100 0 0 100 100\n1 2 0.1\n")
    with pytest.raises(P.ParseError, match=r"bad\.mhap:2.*malformed "
                                           r"MHAP"):
        list(P._parse_mhap_py(str(mhap)))

    sam = tmp_path / "bad.sam"
    sam.write_bytes(b"@HD\tVN:1.6\nq1\tzero\tt1\t1\t60\t4M\n")
    with pytest.raises(P.ParseError, match=r"bad\.sam:2.*malformed SAM"):
        list(P._parse_sam_py(str(sam)))


def test_parse_error_through_public_api_and_native(tmp_path):
    """Through the public parse_* surface (native parser when built,
    Python fallback otherwise) a malformed file still raises a
    ValueError subclass naming the file."""
    import pytest

    import racon_tpu.io.parsers as P

    fq = tmp_path / "pub.fastq"
    fq.write_bytes(b"not a header\nACGT\n+\n!!!!\n")
    with pytest.raises(ValueError, match="malformed FASTQ header"):
        list(P.parse_fastq(str(fq)))

    paf = tmp_path / "pub.paf"
    paf.write_bytes(b"q1\t100\t0\n")
    with pytest.raises(ValueError, match=r"pub\.paf|malformed line"):
        list(P.parse_paf(str(paf)))


def test_span_scanners_report_byte_offsets(tmp_path):
    import pytest

    import racon_tpu.io.parsers as P

    fq = tmp_path / "scan.fastq"
    fq.write_bytes(b"@r1\nACGT\n+\n!!!!\nbroken\n")
    with pytest.raises(P.ParseError, match=r"byte 16") as ei:
        list(P._scan_fastq_spans(str(fq)))
    assert ei.value.offset == 16

    fa = tmp_path / "scan.fasta"
    fa.write_bytes(b"ACGT\n>ctg\nACGT\n")
    with pytest.raises(P.ParseError, match=r"byte 0"):
        list(P._scan_fasta_spans(str(fa)))
