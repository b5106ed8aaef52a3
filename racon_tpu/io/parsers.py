"""Streaming FASTA/FASTQ/MHAP/PAF/SAM parsers with transparent gzip.

Role-equivalent of the reference's vendored ``bioparser`` library (used via
``bioparser::createParser`` at ``src/polisher.cpp:83-133``). ALL five
formats run through the native parser when the C++ core is built
(``native/parsers.cpp``; the Python loops below are the fallback and the
behavioural oracle — ``tests/test_parsers.py`` asserts record-for-record
equality). Matches bioparser's observable behaviour:

- names are truncated at the first whitespace character;
- FASTA/FASTQ records may span multiple lines;
- gzip is detected by magic bytes, not extension;
- extension-based format dispatch lists live in ``SEQUENCE_EXTENSIONS`` /
  ``OVERLAP_EXTENSIONS`` (mirrors ``src/polisher.cpp:83-133``).
"""

from __future__ import annotations

import gzip
import io
from dataclasses import dataclass
from typing import Iterator, NamedTuple, Optional

SEQUENCE_EXTENSIONS = (
    ".fasta", ".fasta.gz", ".fna", ".fna.gz", ".fa", ".fa.gz",
    ".fastq", ".fastq.gz", ".fq", ".fq.gz",
)
FASTQ_EXTENSIONS = (".fastq", ".fastq.gz", ".fq", ".fq.gz")
OVERLAP_EXTENSIONS = (".mhap", ".mhap.gz", ".paf", ".paf.gz", ".sam", ".sam.gz")

# the overlaps-path sentinel selecting the first-party in-process
# overlapper (racon_tpu/ops/overlap_seed.py + chain.py) instead of a
# precomputed PAF/MHAP/SAM file
AUTO_OVERLAPS = "auto"


def is_auto_overlaps(path: str) -> bool:
    """True when ``path`` is the sentinel ``auto`` (no overlaps file is
    read; the overlapper generates rows in memory)."""
    return path == AUTO_OVERLAPS


def overlaps_mode(path: str) -> str:
    """The overlap source an overlaps argument names: ``auto`` for the
    sentinel (what the CLI's ``--overlaps auto`` puts in the file's
    place), else ``paf`` (precomputed-file mode)."""
    return "auto" if is_auto_overlaps(path) else "paf"


class ParseError(ValueError):
    """A malformed input record, carrying structured location info:
    the file, the 1-based line number (Python parsers) and/or the byte
    offset in the decompressed stream (span scanners), so a bad record
    in a 100 GB input is findable without bisecting the file. A
    ``ValueError`` subclass — every existing handler (CLI error paths,
    the shard runner's ladder, tests) keeps working."""

    def __init__(self, path: str, msg: str, line: Optional[int] = None,
                 offset: Optional[int] = None):
        self.path = path
        self.line = line
        self.offset = offset
        self.msg = msg
        loc = path
        if line is not None:
            loc += f":{line}"
        if offset is not None:
            loc += f" (byte {offset})"
        super().__init__(f"{loc}: {msg}")


class SequenceRecord(NamedTuple):
    """One read or contig. A named tuple: the native parsers hand over
    plain ``(name, data, quality)`` tuples, and a record is made of one
    without a Python-level constructor call (345,000 of them a draft Mbp
    in a 50x short-read set)."""
    name: bytes
    data: bytes
    quality: Optional[bytes] = None  # None for FASTA


@dataclass
class OverlapRecord:
    """Raw fields of one overlap line; interpretation happens in core.Overlap."""
    fmt: str  # "paf" | "mhap" | "sam"
    fields: tuple


def open_maybe_gzip(path: str) -> io.BufferedReader:
    f = open(path, "rb")
    magic = f.peek(2)[:2]
    if magic == b"\x1f\x8b":
        f.close()
        return io.BufferedReader(gzip.open(path))  # type: ignore[arg-type]
    return f


def _first_token(line: bytes) -> bytes:
    return line.split(None, 1)[0] if line else b""


def _native_records(path: str, is_fastq: bool):
    # The native parser streams chunked inflate+parse through a bounded
    # rolling buffer (native/parsers.cpp LineReader — the reference
    # bioparser's 1 GiB-chunk analog, src/polisher.cpp:26), so peak RSS
    # is the materialized records plus O(longest line), never the
    # decompressed input. The wrapper's out-of-core split
    # (racon_tpu/wrapper.py) additionally bounds the record set itself.
    from .. import native
    if not native.available():
        return None
    try:
        recs = native.parse_seqfile(path, is_fastq)
    except native.NativeBuildError:
        return None
    except ValueError as e:
        # the native LineReader reports malformed records as plain
        # ValueErrors; re-raise structured with the file attached
        raise ParseError(path, str(e)) from e
    return list(map(SequenceRecord._make, recs))


def parse_fasta(path: str):
    """Iterable of SequenceRecords (a materialized list on the native
    fast path — avoids 1 generator hop per record on huge files)."""
    recs = _native_records(path, False)
    if recs is not None:
        return recs
    return _parse_fasta_py(path)


def _parse_fasta_py(path: str) -> Iterator[SequenceRecord]:
    name = None
    chunks: list = []
    with open_maybe_gzip(path) as f:
        for ln, raw in enumerate(f, 1):
            line = raw.rstrip()
            if not line:
                continue
            if line.startswith(b">"):
                if name is not None:
                    yield SequenceRecord(name, b"".join(chunks))
                name = _first_token(line[1:])
                if not name:
                    raise ParseError(path, "FASTA header with an empty "
                                           "sequence name", line=ln)
                chunks = []
            elif name is None:
                raise ParseError(
                    path, f"sequence data before the first FASTA "
                          f"header: {line[:40]!r}", line=ln)
            else:
                chunks.append(line)
        if name is not None:
            yield SequenceRecord(name, b"".join(chunks))


def parse_fastq(path: str):
    """Multi-line-tolerant FASTQ: sequence lines until '+', then quality bytes
    until their length matches the sequence length."""
    recs = _native_records(path, True)
    if recs is not None:
        return recs
    return _parse_fastq_py(path)


def _parse_fastq_py(path: str) -> Iterator[SequenceRecord]:
    with open_maybe_gzip(path) as f:
        it = iter(f)
        ln = 0

        def nxt():
            nonlocal ln
            line = next(it)
            ln += 1
            return line

        while True:
            try:
                raw = nxt()
            except StopIteration:
                return
            header = raw.rstrip()
            if not header:
                continue
            rec_line = ln
            if not header.startswith(b"@"):
                raise ParseError(
                    path, f"malformed FASTQ header: {header[:40]!r}",
                    line=ln)
            name = _first_token(header[1:])
            seq_chunks = []
            while True:
                try:
                    line = nxt().rstrip()
                except StopIteration:
                    raise ParseError(
                        path, f"truncated FASTQ record for {name!r} "
                              f"(no '+' separator)",
                        line=rec_line) from None
                if line.startswith(b"+"):
                    break
                seq_chunks.append(line)
            data = b"".join(seq_chunks)
            qual_chunks = []
            qlen = 0
            while qlen < len(data):
                try:
                    line = nxt().rstrip()
                except StopIteration:
                    raise ParseError(
                        path, f"truncated FASTQ record for {name!r}",
                        line=rec_line) from None
                qual_chunks.append(line)
                qlen += len(line)
            quality = b"".join(qual_chunks)
            if len(quality) != len(data):
                raise ParseError(
                    path, f"FASTQ quality/sequence length mismatch for "
                          f"{name!r} ({len(quality)} != {len(data)})",
                    line=rec_line)
            yield SequenceRecord(name, data, quality)


def _native_ovl(path: str, fmt_code: int):
    """Native overlap parse (same memory tradeoff note as
    :func:`_native_records`); returns None when the native core is
    unavailable, else the full record list — already ``.fmt``/
    ``.fields`` record objects, materialized in C."""
    from .. import native
    if not native.available():
        return None
    try:
        return native.parse_ovlfile(path, fmt_code)
    except native.NativeBuildError:
        return None
    except ValueError as e:
        raise ParseError(path, str(e)) from e


def parse_paf(path: str):
    """PAF: qname qlen qstart qend strand tname tlen tstart tend matches alen mapq [tags]."""
    recs = _native_ovl(path, 0)
    if recs is not None:
        return recs
    return _parse_paf_py(path)


def _parse_paf_py(path: str) -> Iterator[OverlapRecord]:
    with open_maybe_gzip(path) as f:
        for ln, raw in enumerate(f, 1):
            line = raw.rstrip()
            if not line:
                continue
            t = line.split(b"\t")
            try:
                yield OverlapRecord("paf", (
                    t[0], int(t[1]), int(t[2]), int(t[3]),
                    t[4][:1].decode(),
                    t[5], int(t[6]), int(t[7]), int(t[8]),
                ))
            except (IndexError, ValueError, UnicodeDecodeError) as e:
                raise ParseError(
                    path, f"malformed PAF record ({type(e).__name__}): "
                          f"{line[:60]!r}", line=ln) from e


def parse_mhap(path: str):
    """MHAP: aid bid jaccard shared arc astart aend alen brc bstart bend
    blen (space-separated, 1-based ids)."""
    recs = _native_ovl(path, 1)
    if recs is not None:
        return recs
    return _parse_mhap_py(path)


def _parse_mhap_py(path: str) -> Iterator[OverlapRecord]:
    with open_maybe_gzip(path) as f:
        for ln, raw in enumerate(f, 1):
            line = raw.rstrip()
            if not line:
                continue
            t = line.split()
            try:
                yield OverlapRecord("mhap", (
                    int(t[0]), int(t[1]), float(t[2]), int(t[3]),
                    int(t[4]), int(t[5]), int(t[6]), int(t[7]),
                    int(t[8]), int(t[9]), int(t[10]), int(t[11]),
                ))
            except (IndexError, ValueError) as e:
                raise ParseError(
                    path, f"malformed MHAP record ({type(e).__name__}): "
                          f"{line[:60]!r}", line=ln) from e


def parse_sam(path: str):
    """SAM: qname flag rname pos mapq cigar ... (header lines skipped)."""
    recs = _native_ovl(path, 2)
    if recs is not None:
        return recs
    return _parse_sam_py(path)


def _parse_sam_py(path: str) -> Iterator[OverlapRecord]:
    with open_maybe_gzip(path) as f:
        for ln, raw in enumerate(f, 1):
            if raw.startswith(b"@"):
                continue
            line = raw.rstrip()
            if not line:
                continue
            t = line.split(b"\t")
            try:
                yield OverlapRecord("sam", (
                    t[0], int(t[1]), t[2], int(t[3]), t[5],
                ))
            except (IndexError, ValueError) as e:
                raise ParseError(
                    path, f"malformed SAM record ({type(e).__name__}): "
                          f"{line[:60]!r}", line=ln) from e


# --------------------------------------------------- indexed byte-range IO
#
# The streaming shard runner (racon_tpu.exec) does one cheap metadata pass
# over each input (names + byte spans only, no payloads) and later re-reads
# just the spans a shard needs. Offsets are DECOMPRESSED-stream offsets, so
# the same coordinates work for plain and gzipped files: plain files seek,
# gzipped files take one forward streamed-inflate pass per shard (the
# native chunked-inflate LineReader shares that floor). Spans are copied
# verbatim, so multi-line records, comments and exact quality bytes
# round-trip bit-for-bit.

@dataclass
class RecordSpan:
    """One sequence record's location: ``[start, end)`` byte span in the
    decompressed stream, plus the metadata the index pass needs (name as
    the parser would truncate it, payload base count, quality flag)."""
    name: bytes
    start: int
    end: int
    bases: int
    has_quality: bool = False


def _scan_fasta_spans(path: str) -> Iterator[RecordSpan]:
    pos = 0
    name = None
    start = 0
    bases = 0
    with open_maybe_gzip(path) as f:
        for raw in f:
            line_start = pos
            pos += len(raw)
            line = raw.rstrip()
            if not line:
                continue
            if line.startswith(b">"):
                if name is not None:
                    yield RecordSpan(name, start, line_start, bases)
                name = _first_token(line[1:])
                if not name:
                    raise ParseError(path, "FASTA header with an empty "
                                           "sequence name",
                                     offset=line_start)
                start = line_start
                bases = 0
            elif name is None:
                raise ParseError(
                    path, f"sequence data before the first FASTA "
                          f"header: {line[:40]!r}", offset=line_start)
            else:
                bases += len(line)
        if name is not None:
            yield RecordSpan(name, start, pos, bases)


def _scan_fastq_spans(path: str) -> Iterator[RecordSpan]:
    with open_maybe_gzip(path) as f:
        pos = 0
        it = iter(f)
        for raw in it:
            start = pos
            pos += len(raw)
            header = raw.rstrip()
            if not header:
                continue
            if not header.startswith(b"@"):
                raise ParseError(
                    path, f"malformed FASTQ header: {header[:40]!r}",
                    offset=start)
            name = _first_token(header[1:])
            bases = 0
            for raw in it:
                pos += len(raw)
                line = raw.rstrip()
                if line.startswith(b"+"):
                    break
                bases += len(line)
            qlen = 0
            while qlen < bases:
                try:
                    raw = next(it)
                except StopIteration:
                    raise ParseError(
                        path, f"truncated FASTQ record for {name!r}",
                        offset=start) from None
                pos += len(raw)
                qlen += len(raw.rstrip())
            yield RecordSpan(name, start, pos, bases, True)


def scan_sequence_spans(path: str):
    """Record-span scan of a FASTA/FASTQ file (same extension dispatch,
    name truncation and multi-line tolerance as the real parsers — the
    spans of two adjacent records tile the file). Returns an iterator of
    :class:`RecordSpan`, or None for unsupported extensions."""
    if _has_suffix(path, FASTQ_EXTENSIONS):
        return _scan_fastq_spans(path)
    if _has_suffix(path, SEQUENCE_EXTENSIONS):
        return _scan_fasta_spans(path)
    return None


def scan_line_spans(path: str) -> Iterator[tuple]:
    """``(start, end, stripped_line)`` per raw line of a (possibly
    gzipped) text file — the overlap-index pass walks PAF/MHAP/SAM files
    through this so kept lines can later be copied verbatim by span."""
    pos = 0
    with open_maybe_gzip(path) as f:
        for raw in f:
            start = pos
            pos += len(raw)
            yield start, pos, raw.rstrip()


def iter_byte_ranges(path: str, ranges) -> Iterator[bytes]:
    """Yield the raw decompressed bytes of each sorted, non-overlapping
    ``(start, end)`` range. Plain files seek straight to each range;
    gzipped files take a single forward pass (inflate cannot seek)."""
    f = open(path, "rb")
    try:
        if f.peek(2)[:2] == b"\x1f\x8b":
            with io.BufferedReader(gzip.open(f)) as g:  # type: ignore[arg-type]
                pos = 0
                for start, end in ranges:
                    if start < pos:
                        raise ValueError("ranges must be sorted and "
                                         "non-overlapping")
                    while pos < start:
                        skipped = len(g.read(min(1 << 20, start - pos)))
                        if not skipped:
                            raise ValueError(f"range past EOF in {path}")
                        pos += skipped
                    parts = []
                    while pos < end:
                        chunk = g.read(min(1 << 20, end - pos))
                        if not chunk:
                            raise ValueError(f"range past EOF in {path}")
                        parts.append(chunk)
                        pos += len(chunk)
                    yield b"".join(parts)
        else:
            with f:
                for start, end in ranges:
                    f.seek(start)
                    data = f.read(end - start)
                    if len(data) != end - start:
                        raise ValueError(f"range past EOF in {path}")
                    yield data
    finally:
        f.close()


def copy_byte_ranges(path: str, ranges, out) -> int:
    """Append each range's raw bytes to the binary stream ``out``;
    returns the byte count copied."""
    total = 0
    for blob in iter_byte_ranges(path, ranges):
        out.write(blob)
        total += len(blob)
    return total


def _has_suffix(path: str, suffixes) -> bool:
    return any(path.endswith(s) for s in suffixes)


def sequence_parser_for(path: str):
    """Extension dispatch for sequence files (``src/polisher.cpp:83-99``).

    Returns a generator factory, or None for unsupported extensions."""
    if _has_suffix(path, FASTQ_EXTENSIONS):
        return parse_fastq
    if _has_suffix(path, SEQUENCE_EXTENSIONS):
        return parse_fasta
    return None


def overlap_parser_for(path: str):
    """Extension dispatch for overlap files (``src/polisher.cpp:101-115``)."""
    if _has_suffix(path, (".mhap", ".mhap.gz")):
        return parse_mhap
    if _has_suffix(path, (".paf", ".paf.gz")):
        return parse_paf
    if _has_suffix(path, (".sam", ".sam.gz")):
        return parse_sam
    return None
