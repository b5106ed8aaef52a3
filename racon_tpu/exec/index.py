"""Contig->overlap index: the cheap first pass of a streaming shard run.

One forward scan of each input file records **metadata only** (names,
decompressed byte spans, base counts — never payloads), then walks the
overlap file applying the polisher's *global* filter semantics so that a
per-shard run later sees exactly the overlaps a single-shot run would
keep. That global replay is the heart of the shard-count-invariance
contract; the rules it mirrors, with their single-shot sources:

- name/id resolution (``Polisher._initialize_core``): queries resolve
  against the read set — a read whose name matches a target collapses
  onto the target's record (``name_to_id[name + b'q'] = tid``); MHAP
  queries resolve by raw file ordinal (``id_to_id``), PAF/SAM by name
  with later duplicates winning (dict overwrite order);
- validity (``Overlap.transmute``): an unresolvable query or target name
  invalidates the line *before* grouping — invalid lines do not split a
  query group;
- the per-group filter (``Polisher._filter_overlaps``): groups are
  maximal runs of consecutive VALID lines sharing a resolved query
  identity; error > threshold and self overlaps drop inside the group;
  contig polishing then keeps one overlap per group — the longest, later
  line winning length ties.

Shards built from this index run their polisher with
``prefiltered_overlaps=True``: re-running the group filter on a shard's
subsequence could merge groups that were split in the original stream
and flip the best-per-group choice.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from .. import sanitize
from ..core.polisher import PolisherType
from ..core.window import WindowType
from ..io import parsers
from ..utils.cigar import parse_cigar


@dataclass
class OverlapLine:
    """Minimal per-line facts the global filter needs."""
    start: int
    end: int
    t_idx: int
    q_ord: int        # read-file ordinal of the record the query resolves to
    length: int
    error: float
    is_self: bool


@dataclass
class RunIndex:
    """Everything the planner and runner need, O(records) metadata only."""
    sequences_path: str
    overlaps_path: str
    target_path: str
    overlap_fmt: str                       # "paf" | "mhap" | "sam"
    targets: List[parsers.RecordSpan]
    read_spans: np.ndarray                 # (R, 3) int64: start, end, bases
    read_names: List[bytes]
    window_type: WindowType
    # kept overlaps, file order (parallel int64 arrays)
    ov_start: np.ndarray = field(default_factory=lambda: np.zeros(0, np.int64))
    ov_end: np.ndarray = field(default_factory=lambda: np.zeros(0, np.int64))
    ov_target: np.ndarray = field(default_factory=lambda: np.zeros(0, np.int64))
    ov_read: np.ndarray = field(default_factory=lambda: np.zeros(0, np.int64))
    # reads-only planning (--overlaps auto before the overlapper ran):
    # total read bases to apportion across contigs by contig size when
    # no per-contig overlap groups exist yet
    uniform_read_bases: int = 0
    _groups: Optional[dict] = field(default=None, repr=False, compare=False)

    def __post_init__(self):
        # concurrent chip workers extract shards from ONE index: the
        # lazy group build must happen once, not once per drain thread
        # (the whole-run argsort is the expensive part)
        self._groups_lock = sanitize.named_lock("exec.index")

    def _contig_groups(self) -> dict:
        """contig index -> kept-overlap index array (file order inside
        each group). ONE stable argsort for the whole run — per-contig
        masks would be O(n_contigs * n_overlaps), quadratic at the
        genome scale this subsystem targets (-f mode makes every read a
        target, pushing n_contigs into the millions)."""
        with self._groups_lock:
            if self._groups is None:
                order = np.argsort(self.ov_target, kind="stable")
                st = self.ov_target[order]
                starts = np.flatnonzero(np.r_[True, np.diff(st) != 0]) \
                    if st.size else np.zeros(0, np.int64)
                bounds = list(starts) + [st.size]
                self._groups = {int(st[a]): order[a:b]
                                for a, b in zip(bounds, bounds[1:])}
            return self._groups

    def lines_of_contig(self, t_idx: int) -> np.ndarray:
        """Kept-overlap indices of one contig, in file order."""
        return self._contig_groups().get(t_idx, np.zeros(0, np.int64))

    def contig_overlap_bytes(self) -> np.ndarray:
        """Per-contig kept-overlap byte counts (planner cost term)."""
        out = np.zeros(len(self.targets), np.int64)
        np.add.at(out, self.ov_target, self.ov_end - self.ov_start)
        return out

    def contig_read_bytes(self) -> np.ndarray:
        """Per-contig unique-read base counts (planner cost term; a read
        shared by two contigs is charged to both — shard costs are an
        upper bound, recomputed on the union after packing)."""
        out = np.zeros(len(self.targets), np.int64)
        if self.uniform_read_bases and not self.ov_read.size:
            # no overlaps indexed yet (--overlaps auto planning): charge
            # read bases to contigs proportionally to contig size
            tb = np.fromiter((t.bases for t in self.targets), np.int64,
                             len(self.targets))
            total = max(1, int(tb.sum()))
            return tb * self.uniform_read_bases // total
        for t, g in self._contig_groups().items():
            out[t] = int(self.read_spans[np.unique(self.ov_read[g]),
                                         2].sum())
        return out


def _overlap_fmt(path: str) -> str:
    parser = parsers.overlap_parser_for(path)
    if parser is parsers.parse_paf:
        return "paf"
    if parser is parsers.parse_mhap:
        return "mhap"
    if parser is parsers.parse_sam:
        return "sam"
    raise ValueError(
        f"file {path} has unsupported format extension (valid: "
        f"{', '.join(parsers.OVERLAP_EXTENSIONS)})")


def _sam_stats(cigar: bytes) -> Tuple[int, int]:
    """(q_aln, t_aln) from a SAM CIGAR — the span inputs of the error
    formula (mirrors ``Overlap.from_sam``)."""
    q_aln = t_aln = 0
    for n, op in parse_cigar(cigar.decode()):
        if op in ("M", "=", "X"):
            q_aln += n
            t_aln += n
        elif op == "I":
            q_aln += n
        elif op in ("D", "N"):
            t_aln += n
    return q_aln, t_aln


def _span_error(q_span: int, t_span: int) -> Tuple[int, float]:
    """(length, error) exactly as ``Overlap._set_error`` computes them."""
    length = max(q_span, t_span)
    error = 1 - min(q_span, t_span) / float(length) if length else 1.0
    return length, error


def build_index(sequences_path: str, overlaps_path: str, target_path: str,
                type_: PolisherType = PolisherType.C,
                error_threshold: float = 0.3) -> RunIndex:
    """One metadata pass over the three inputs; raises the same
    empty-set errors a single-shot ``initialize()`` would."""
    tscan = parsers.scan_sequence_spans(target_path)
    if tscan is None:
        raise ValueError(f"file {target_path} has unsupported format "
                         f"extension")
    targets = list(tscan)
    if not targets:
        raise ValueError("empty target sequences set")
    # later duplicate target names win (dict overwrite — matches
    # name_to_id construction order in the polisher)
    target_ids: Dict[bytes, int] = {t.name: i for i, t in enumerate(targets)}

    rscan = parsers.scan_sequence_spans(sequences_path)
    if rscan is None:
        raise ValueError(f"file {sequences_path} has unsupported format "
                         f"extension")
    read_names: List[bytes] = []
    spans: List[Tuple[int, int, int]] = []
    total_len = 0
    for rec in rscan:
        read_names.append(rec.name)
        spans.append((rec.start, rec.end, rec.bases))
        total_len += rec.bases
    if not read_names:
        raise ValueError("empty sequences set")
    read_spans = np.asarray(spans, np.int64).reshape(-1, 3)
    window_type = WindowType.of_reads(total_len, len(read_names))
    # PAF/SAM queries resolve by name, later duplicates winning
    read_ids: Dict[bytes, int] = {n: i for i, n in enumerate(read_names)}

    fmt = _overlap_fmt(overlaps_path)
    lines = _scan_overlaps(overlaps_path, fmt, targets, target_ids,
                           read_names, read_ids)
    kept = _global_filter(lines, type_, error_threshold)
    if not kept:
        raise ValueError("empty overlap set")

    idx = RunIndex(sequences_path, overlaps_path, target_path, fmt,
                   targets, read_spans, read_names, window_type)
    idx.ov_start = np.fromiter((l.start for l in kept), np.int64, len(kept))
    idx.ov_end = np.fromiter((l.end for l in kept), np.int64, len(kept))
    idx.ov_target = np.fromiter((l.t_idx for l in kept), np.int64, len(kept))
    idx.ov_read = np.fromiter((l.q_ord for l in kept), np.int64, len(kept))
    return idx


def _scan_overlaps(path: str, fmt: str, targets, target_ids, read_names,
                   read_ids) -> List[Tuple[Tuple, OverlapLine]]:
    """Valid overlap lines in file order, each tagged with its resolved
    query identity (the group key). Invalid lines are dropped here —
    they do not split groups, exactly like the polisher's
    ``if o.is_valid`` append gate."""
    out: List[Tuple[Tuple, OverlapLine]] = []
    n_reads = len(read_names)
    for start, end, line in parsers.scan_line_spans(path):
        if not line:
            continue
        if fmt == "sam" and line.startswith(b"@"):
            continue
        if fmt == "mhap":
            f = line.split()
            a_ord, t_idx = int(f[0]) - 1, int(f[1]) - 1
            if not (0 <= a_ord < n_reads) or not (0 <= t_idx < len(targets)):
                continue
            q_name = read_names[a_ord]
            length, error = _span_error(int(f[6]) - int(f[5]),
                                        int(f[10]) - int(f[9]))
            q_ord = a_ord  # MHAP resolves by raw ordinal (id_to_id)
        else:
            f = line.split(b"\t")
            q_name = f[0]  # verbatim, like the PAF/SAM record parsers
            if fmt == "paf":
                t_name = f[5]
                length, error = _span_error(int(f[3]) - int(f[2]),
                                            int(f[8]) - int(f[7]))
            else:  # sam
                if int(f[1]) & 0x4:
                    continue  # unmapped: is_valid False before transmute
                t_name = f[2]
                if len(f[5]) < 2:
                    raise ValueError("missing alignment from SAM record")
                length, error = _span_error(*_sam_stats(f[5]))
            q_ord = read_ids.get(q_name, -1)
            t_idx = target_ids.get(t_name, -1)
            if q_ord < 0 or t_idx < 0:
                continue  # unresolvable name: invalid before grouping
        # group identity: a read named like a target collapses onto the
        # target record (the polisher's name_to_id[name + b"q"] = tid)
        tgt = target_ids.get(q_name)
        identity = (("t", tgt) if tgt is not None else ("r", q_ord))
        out.append((identity, OverlapLine(
            start, end, t_idx, q_ord, length, error,
            is_self=identity == ("t", t_idx))))
    return out


def _global_filter(lines, type_: PolisherType,
                   error_threshold: float) -> List[OverlapLine]:
    """Replay ``Polisher._filter_overlaps`` over the whole stream."""
    kept: List[OverlapLine] = []

    def flush(group: List[OverlapLine]) -> None:
        passing = [l for l in group
                   if l.error <= error_threshold and not l.is_self]
        if not passing:
            return
        if type_ == PolisherType.C:
            best = passing[0]
            for l in passing[1:]:
                if l.length >= best.length:  # later line wins ties
                    best = l
            kept.append(best)
        else:
            kept.extend(passing)

    cur_id: Optional[Tuple] = None
    group: List[OverlapLine] = []
    for identity, line in lines:
        if identity != cur_id:
            flush(group)
            cur_id, group = identity, []
        group.append(line)
    flush(group)
    kept.sort(key=lambda l: l.start)  # back to file order across groups
    return kept


# ------------------------------------------- first-party overlapper mode

def write_auto_paf(sequences_path: str, target_path: str,
                   paf_path: str) -> None:
    """``--overlaps auto`` for shard runs: run the first-party
    overlapper (:mod:`racon_tpu.ops.chain`) over the inputs and write
    its rows as a 12-column PAF — deterministic bytes, atomically
    replaced, so reruns and concurrent workers converge on the same
    file and the resume fingerprint (path + size) stays stable."""
    from ..ops import chain as chain_ops
    tparse = parsers.sequence_parser_for(target_path)
    sparse = parsers.sequence_parser_for(sequences_path)
    if tparse is None or sparse is None:
        raise ValueError("unsupported sequence format extension")
    target_names: List[bytes] = []
    target_seqs: List[bytes] = []
    for rec in tparse(target_path):
        target_names.append(rec.name)
        target_seqs.append(rec.data)
    target_ids = {n: i for i, n in enumerate(target_names)}
    read_names: List[bytes] = []
    read_seqs: List[bytes] = []
    for rec in sparse(sequences_path):
        read_names.append(rec.name)
        read_seqs.append(rec.data)
    read_self_t = np.fromiter(
        (target_ids.get(n, -1) for n in read_names), np.int64,
        len(read_names))
    rows = chain_ops.find_overlaps(read_seqs, target_seqs, read_self_t)
    from .. import flags
    k = max(4, min(16, flags.get_int("RACON_TPU_OVERLAP_K")))
    lines = chain_ops.paf_bytes(
        rows, read_names,
        np.fromiter((len(s) for s in read_seqs), np.int64,
                    len(read_seqs)),
        target_names,
        np.fromiter((len(s) for s in target_seqs), np.int64,
                    len(target_seqs)), k=k)
    from .manifest import atomic_write
    atomic_write(paf_path, b"".join(lines))


def build_index_auto(sequences_path: str, target_path: str,
                     paf_path: str, type_: PolisherType = PolisherType.C,
                     error_threshold: float = 0.3) -> RunIndex:
    """``--overlaps auto`` index: materialize the overlapper's rows as
    a deterministic PAF in the work dir, then index THAT file with the
    ordinary :func:`build_index` — the global-filter replay and every
    byte-span consumer (shard extraction, resume fingerprints) see a
    real overlaps file, so shard-count invariance needs no new path."""
    import os
    if not os.path.isfile(paf_path):
        write_auto_paf(sequences_path, target_path, paf_path)
    return build_index(sequences_path, paf_path, target_path, type_,
                       error_threshold)


def build_index_readsonly(sequences_path: str,
                          target_path: str) -> RunIndex:
    """Metadata-only index for planning an ``--overlaps auto`` run
    before the overlapper has produced anything: targets + read spans
    with :attr:`RunIndex.uniform_read_bases` set, so the planner's cost
    model works from reads + target sizes alone."""
    tscan = parsers.scan_sequence_spans(target_path)
    if tscan is None:
        raise ValueError(f"file {target_path} has unsupported format "
                         f"extension")
    targets = list(tscan)
    if not targets:
        raise ValueError("empty target sequences set")
    rscan = parsers.scan_sequence_spans(sequences_path)
    if rscan is None:
        raise ValueError(f"file {sequences_path} has unsupported format "
                         f"extension")
    read_names: List[bytes] = []
    spans: List[Tuple[int, int, int]] = []
    total_len = 0
    for rec in rscan:
        read_names.append(rec.name)
        spans.append((rec.start, rec.end, rec.bases))
        total_len += rec.bases
    if not read_names:
        raise ValueError("empty sequences set")
    read_spans = np.asarray(spans, np.int64).reshape(-1, 3)
    window_type = WindowType.of_reads(total_len, len(read_names))
    idx = RunIndex(sequences_path, parsers.AUTO_OVERLAPS, target_path,
                   "paf", targets, read_spans, read_names, window_type)
    idx.uniform_read_bases = total_len
    return idx
