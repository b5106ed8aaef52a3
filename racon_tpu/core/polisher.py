"""Polisher: the two-phase pipeline driver (initialize -> polish).

Behavioural spec from the reference's ``src/polisher.cpp``:

- factory validates extensions then builds the CPU or accelerated pipeline
  (``polisher.cpp:55-159``);
- ``initialize()`` (``polisher.cpp:191-459``): load targets, load reads with
  name-dedup against targets, NGS/TGS window-type heuristic (mean read length
  <= 1000 -> NGS), load + transmute overlaps with streaming per-query
  filtering (error > threshold, self-overlaps, best-per-query for contig
  polishing), lazy reverse-complement materialization, breaking-point
  alignment, window construction and layer assignment (min-span 2% of window
  length, mean PHRED quality >= threshold);
- ``polish()`` (``polisher.cpp:485-547``): per-window consensus via the
  backend, stitch per target, emit ``LN:i/RC:i/XC:f`` tags.

Host init is **columnar** (round 7): breaking points travel as flat int32
row arrays end-to-end (device tables -> ``Overlap.breaking_points`` ->
one concatenated (P, 4) matrix), the min-span and mean-PHRED layer filters
and all window arithmetic run vectorized over that matrix (quality means
via per-read prefix sums), and layers group into windows through a single
stable argsort — no per-overlap/per-pair Python loop. ``run()``
additionally pipelines initialize -> polish: the layer assembly streams
completed window ranges through a bounded queue into the consensus
engine, while the background consensus warm-up compile overlaps the
device alignment (reference
analog: the CUDA polisher overlaps its aligner batches with host work
and streams windows into the polisher, ``cudapolisher.cpp:86-228``).

Memory contract (reference analog: 1 GiB parse chunks,
``polisher.cpp:26,227-263``): the parsers stream records line-by-line
(never the whole file), overlaps release their CIGAR the moment breaking
points are derived and their breaking-point rows once window layers are
assembled; the device aligner sees the overlap stream in bounded 64k-pair
slices, so transient span copies stay O(slice). Like the reference, the
full sequence set stays resident (windows hold views into it); the
wrapper's ``--split`` bounds that too.
"""

from __future__ import annotations

import enum
import sys
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from .. import faults, flags, obs, sanitize
from ..io import parsers
from ..obs import metrics
from ..utils.logger import Logger
from .backends import make_aligner, make_consensus
from .layers import LayerStore, PreparedPool
from .overlap import Overlap, decode_breaking_points_batch
from .readset import ReadSet
from .sequence import Sequence
from .window import Window, WindowType


class _PrepareAhead:
    """One :meth:`Polisher._prepare_layers` call on a thread of its own:
    the overlap list it serves, the thread, and what the call returned
    or raised (written by the thread, read after its join)."""

    __slots__ = ("overlaps", "thread", "result", "error")

    def __init__(self, overlaps):
        self.overlaps = overlaps
        self.thread: Optional[threading.Thread] = None
        self.result: Optional[PreparedPool] = None
        self.error: Optional[BaseException] = None


class PolisherType(enum.Enum):
    C = 0  # contig polishing
    F = 1  # fragment (read) error correction


def create_polisher(sequences_path: str, overlaps_path: str, target_path: str,
                    type_: PolisherType = PolisherType.C,
                    window_length: int = 500, quality_threshold: float = 10.0,
                    error_threshold: float = 0.3, trim: bool = True,
                    match: int = 3, mismatch: int = -5, gap: int = -4,
                    num_threads: int = 1, aligner_backend: str = "auto",
                    consensus_backend: str = "auto", aligner_batches: int = 1,
                    consensus_batches: int = 1,
                    banded: bool = False, *, aligner=None, consensus=None,
                    window_type=None, prefiltered_overlaps: bool = False,
                    evict_reads: bool = False,
                    stall_escalation: bool = False,
                    reads: Optional[ReadSet] = None,
                    targets: Optional[List[Sequence]] = None,
                    final: bool = True) -> "Polisher":
    """Factory with the reference's validation rules
    (``polisher.cpp:62-133``). ``aligner_batches``/``consensus_batches``
    are the accelerator batch counts (reference ``-c N`` /
    ``--cudaaligner-batches N``, ``cudapolisher.cpp:91,215-228``) — here
    the device pipeline depth, with the memory budget split per batch;
    ``banded`` is the reference's ``-b`` POA banding approximation.

    The keyword-only tail is the streaming shard runner's per-shard
    reuse surface (``racon_tpu.exec``): ``aligner``/``consensus`` inject
    prebuilt engines (jit caches and warm-up compiles survive across
    shards), ``window_type`` pins the NGS/TGS heuristic to the
    whole-input decision (a shard's read subset must not flip it),
    ``prefiltered_overlaps`` marks the overlap stream as already
    globally filtered (the runner's index pass applied the
    best-per-query-group rule over the FULL file — re-running it on a
    shard's subsequence could merge groups split in the original
    stream), ``evict_reads`` releases read payloads the moment
    their window layers are assembled, and ``stall_escalation`` arms
    the sanitizer queue watchdog's second-timeout escalation (a
    persistent stall fails the run with a ``stall``-class
    :class:`racon_tpu.faults.StallError` for the runner's degradation
    ladder — standalone runs keep the passive dump-only watchdog).

    ``reads`` / ``targets`` / ``final`` make the polisher one round of
    a ``--rounds N`` job (``cli.main`` owns the loop): ``reads`` is
    the job's :class:`~racon_tpu.core.readset.ReadSet` in place of a
    parse of ``sequences_path``, ``targets`` the round before's
    polished contigs in place of a parse of ``target_path``, and
    ``final`` off marks a polisher with another one behind it on the
    same engines — a round before the last, or a shard of the shard
    runner — which waits for no warm-up at ``stitch`` (the engines are
    handed on, warm-up and all; the last round drains, or the runner's
    slot once it has no shard left) and, where it was given the job's
    ``reads``, leaves every read whole for the next round."""
    if not isinstance(type_, PolisherType):
        raise ValueError("invalid polisher type")
    if window_length <= 0:
        raise ValueError("invalid window length")
    for path, kind in ((sequences_path, "sequences"), (target_path, "target")):
        if parsers.sequence_parser_for(path) is None:
            raise ValueError(
                f"file {path} has unsupported format extension (valid: "
                f"{', '.join(parsers.SEQUENCE_EXTENSIONS)})")
    if parsers.overlaps_mode(overlaps_path) != "auto" \
            and parsers.overlap_parser_for(overlaps_path) is None:
        raise ValueError(
            f"file {overlaps_path} has unsupported format extension (valid: "
            f"{', '.join(parsers.OVERLAP_EXTENSIONS)}, or the literal "
            f"'auto' for the first-party overlapper)")
    return Polisher(sequences_path, overlaps_path, target_path, type_,
                    window_length, quality_threshold, error_threshold, trim,
                    match, mismatch, gap, num_threads, aligner_backend,
                    consensus_backend, aligner_batches, consensus_batches,
                    banded, aligner=aligner, consensus=consensus,
                    window_type=window_type,
                    prefiltered_overlaps=prefiltered_overlaps,
                    evict_reads=evict_reads,
                    stall_escalation=stall_escalation,
                    reads=reads, targets=targets, final=final)


# estimated pairs below which a job kicks no consensus warm-up: the whole
# polish costs less than the compile the warm-up would race to hide
WARMUP_MIN_PAIRS = 16384

# overlaps the streamed hand-off collects before it hands the align
# session a batch (cut after a whole query's rows)
STREAM_FEED_OVERLAPS = 512


def _est_chain_seeds(est_len: int) -> int:
    """Seeds the densest candidate pair of a read set whose longest
    read has ``est_len`` bases will hold, for the chain warm-up: a
    k-mer of 15 survives a 12 % read against a 10 % draft about one
    time in thirty, so of a read's ``est_len / 3`` minimizers one in
    32 is a fair ceiling (2 Mbp at 30x: longest read 8.2 kb, densest
    pair under 256 seeds)."""
    return max(1, est_len // 96)


class Polisher:
    def __init__(self, sequences_path, overlaps_path, target_path, type_,
                 window_length, quality_threshold, error_threshold, trim,
                 match, mismatch, gap, num_threads,
                 aligner_backend="auto", consensus_backend="auto",
                 aligner_batches=1, consensus_batches=1, banded=False,
                 aligner=None, consensus=None, window_type=None,
                 prefiltered_overlaps=False, evict_reads=False,
                 stall_escalation=False, reads=None, targets=None,
                 final=True):
        self.sequences_path = sequences_path
        self.overlaps_path = overlaps_path
        self.target_path = target_path
        self.type = type_
        self.window_length = window_length
        self.quality_threshold = quality_threshold
        self.error_threshold = error_threshold
        self.trim = trim
        self.match, self.mismatch, self.gap = match, mismatch, gap
        self.num_threads = num_threads
        self.aligner = aligner if aligner is not None else make_aligner(
            aligner_backend, num_threads, num_batches=aligner_batches)
        self.consensus = consensus if consensus is not None else \
            make_consensus(consensus_backend, match, mismatch, gap,
                           num_threads, num_batches=consensus_batches,
                           banded=banded)
        # shard-run hooks (see create_polisher)
        self._window_type_override = window_type
        self.prefiltered_overlaps = prefiltered_overlaps
        self.evict_reads = evict_reads
        self.stall_escalation = stall_escalation
        # one round of a --rounds job (see create_polisher)
        self._reads: Optional[ReadSet] = reads
        self._targets: Optional[List[Sequence]] = targets
        self._final = final
        # when a follow-up round had its targets and its reads indexed
        # (perf_counter_ns; the loop's ``round.handoff`` ends there)
        self.handoff_end_ns: Optional[int] = None
        # overlaps left after the filter (the report's rounds rows)
        self.overlaps_kept = 0
        self.logger = Logger()

        self.sequences: List[Sequence] = []
        self.windows: List[Window] = []
        self.targets_size = 0
        self.targets_coverages: List[int] = []
        self._window_type = WindowType.TGS
        self._dummy_quality = b"!" * window_length
        self._id_to_first_window: Optional[np.ndarray] = None
        self._window_lengths: Optional[np.ndarray] = None
        self._backbone_s = 0.0
        # init-phase wall-clock breakdown (parse_s, align_s, bp_decode_s,
        # build_windows_s, pipeline_overlap_saved_s): the run report's
        # ``phases`` section
        self.timings: Dict[str, float] = {}
        # _prepare_layers started ahead of the aligner (_start_prepare),
        # until _assemble_layers takes it
        self._prepare_ahead: Optional[_PrepareAhead] = None

    # ---------------------------------------------------------- initialize

    def initialize(self) -> None:
        """Load, filter, align and window the inputs (synchronous surface;
        :meth:`run` pipelines the same phases against polish)."""
        if self.windows:
            # warning on stderr: stdout carries the polished FASTA
            print("[racon_tpu::Polisher::initialize] warning: "
                  "object already initialized!", file=sys.stderr)
            return
        overlaps = self._initialize_core()
        self.logger.log()
        with obs.span("build.windows"):
            self._assemble_layers(overlaps)
        self.logger.log("[racon_tpu::Polisher::initialize] "
                        "transformed data into windows")

    def _initialize_core(self) -> List[Overlap]:
        """Every initialize phase up to (and including) breaking points:
        parse, filter, transmute, overlap alignment + columnar decode,
        then the backbone-window build. Returns the filtered overlap set,
        ready for layer assembly."""
        log = self.logger
        log.log()
        t_parse = time.perf_counter()

        follow_up = self._targets is not None
        if not follow_up:
            with obs.span("parse.targets"):
                tparse = parsers.sequence_parser_for(self.target_path)
                self.sequences = [Sequence(r.name, r.data, r.quality)
                                  for r in tparse(self.target_path)]
        else:
            # the round before's contigs, as a parse of its FASTA would
            # hand them over: a header's name ends at the first blank,
            # so the LN / RC / XC tags go
            self.sequences = [Sequence(s.name.split(None, 1)[0], s.data)
                              for s in self._targets]
            self._targets = None
        self.targets_size = len(self.sequences)
        if self.targets_size == 0:
            raise ValueError("empty target sequences set")

        name_to_id: Dict[bytes, int] = {}
        id_to_id: Dict[int, int] = {}
        for i, seq in enumerate(self.sequences):
            name_to_id[seq.name + b"t"] = i
            id_to_id[i << 1 | 1] = i

        has_name = [True] * self.targets_size
        has_data = [True] * self.targets_size
        has_reverse = [False] * self.targets_size

        log.log("[racon_tpu::Polisher::initialize] loaded target sequences")
        log.log()

        if self._reads is None:
            with obs.span("parse.reads"):
                sparse = parsers.sequence_parser_for(self.sequences_path)
                raw_index, total_len = self._index_reads(
                    (Sequence(rec.name, rec.data, rec.quality)
                     for rec in sparse(self.sequences_path)),
                    name_to_id, id_to_id, has_name, has_data, has_reverse)
            metrics.inc("rounds.reads_parsed", raw_index)
        else:
            raw_index, total_len = self._index_reads(
                self._reads.sequences(), name_to_id, id_to_id, has_name,
                has_data, has_reverse)
        if follow_up:
            self.handoff_end_ns = time.perf_counter_ns()

        if raw_index == 0:
            raise ValueError("empty sequences set")

        self._window_type = WindowType.of_reads(total_len, raw_index)
        if self._window_type_override is not None:
            # shard runs pin the heuristic to the whole-input decision:
            # a shard's read subset must not flip NGS/TGS mid-assembly
            self._window_type = self._window_type_override
        metrics.set_gauge("polisher.window_type", self._window_type.value)

        log.log("[racon_tpu::Polisher::initialize] loaded sequences")
        log.log()

        auto_mode = parsers.overlaps_mode(self.overlaps_path) == "auto"
        stream_auto = (auto_mode and not self.prefiltered_overlaps
                       and flags.get_bool("RACON_TPU_OVERLAP_RAGGED"))
        if stream_auto:
            # streaming overlap->align hand-off: filtered overlap rows
            # come off the chain stream per fetched chunk and feed the
            # align session incrementally — the last chain chunks,
            # filtering, and alignment dispatch interleave instead of
            # phase-barriering
            overlaps = self._generate_overlaps_stream(
                raw_index, name_to_id, id_to_id,
                has_name, has_data, has_reverse, t_parse)
        else:
            if auto_mode:
                overlaps = self._generate_overlaps(raw_index, name_to_id,
                                                   id_to_id)
            else:
                with obs.span("parse.overlaps"):
                    oparse = parsers.overlap_parser_for(self.overlaps_path)
                    overlaps = []
                    for rec in oparse(self.overlaps_path):
                        o = Overlap.from_record(rec)
                        o.transmute(self.sequences, name_to_id, id_to_id)
                        if o.is_valid:
                            overlaps.append(o)

            with obs.span("overlap.filter"):
                if not self.prefiltered_overlaps:
                    overlaps = self._filter_overlaps(overlaps)
                if auto_mode:
                    metrics.inc("overlap.queries_kept",
                                len({o.q_id for o in overlaps}))
            if not overlaps:
                raise ValueError("empty overlap set")

            for o in overlaps:
                if o.strand:
                    has_reverse[o.q_id] = True
                else:
                    has_data[o.q_id] = True

            log.log("[racon_tpu::Polisher::initialize] loaded overlaps")
            log.log()

            self._kick_consensus_warmup(
                sum(o.length // self.window_length + 1 for o in overlaps),
                max(o.length for o in overlaps))
            self._transmute_all(has_name, has_data, has_reverse)

            # builder-path writes (here through _assemble_layers) run on
            # EITHER the main thread (initialize()/polish()) OR run()'s
            # single producer thread — never both: exactly one builder
            # runs per polisher, and the queue sentinel orders its last
            # write before the consumer continues
            # graftlint: disable=lock-discipline (one builder thread per polisher; paths are alternatives, ordered by the queue sentinel)
            self.timings["parse_s"] = round(
                time.perf_counter() - t_parse, 3)

            # the overlap set is complete and its reads are transmuted:
            # with a thread to spare, the half of the layer assembly that
            # needs no breaking point runs beside the aligner (the
            # streamed feed above, still receiving overlaps while it
            # aligns, leaves it to _assemble_layers)
            if self.num_threads > 1:
                self._start_prepare(overlaps)
            try:
                self.find_overlap_breaking_points(overlaps)
            except BaseException as e:
                # a wedged run is abandoned as run() abandons its
                # producer; any other failure retires the thread first
                self._drop_prepare(
                    wait=not isinstance(e, faults.StallError))
                raise

        # backbone windows build AFTER alignment: a failed alignment then
        # leaves self.windows empty, so the double-init guard stays
        # accurate and the polisher is cleanly re-initializable
        t_bb = time.perf_counter()
        with obs.span("build.backbone"):
            self._build_backbone_windows()
        self._backbone_s = time.perf_counter() - t_bb
        # meaningful only for run(): layer-assembly wall hidden under the
        # consensus engine (the split surface overlaps nothing)
        self.timings.setdefault("pipeline_overlap_saved_s", 0.0)
        self.overlaps_kept = len(overlaps)
        return overlaps

    def _index_reads(self, reads, name_to_id: Dict[bytes, int],
                     id_to_id: Dict[int, int], has_name, has_data,
                     has_reverse) -> tuple:
        """Append ``reads`` (``Sequence`` objects in file order) to
        ``self.sequences`` behind the targets and key them by name and
        by ordinal (a read named like a target IS that target:
        ``polisher.cpp:227-263``). Returns ``(reads, their bases)``.
        A round with another one behind it marks every read as needed
        whole, so :meth:`_transmute_all` frees nothing of it (a shard
        with another one behind it shares engines, not reads)."""
        keep = self._reads is not None and not self._final
        raw_index = 0
        total_len = 0
        for seq in reads:
            total_len += len(seq.data)
            tid = name_to_id.get(seq.name + b"t")
            if tid is not None:
                existing = self.sequences[tid]
                if (len(seq.data) != len(existing.data) or
                        len(seq.quality or b"")
                        != len(existing.quality or b"")):
                    raise ValueError(
                        f"duplicate sequence {seq.name!r} with "
                        f"unequal data")
                name_to_id[seq.name + b"q"] = tid
                id_to_id[raw_index << 1 | 0] = tid
            else:
                self.sequences.append(seq)
                pos = len(self.sequences) - 1
                name_to_id[seq.name + b"q"] = pos
                id_to_id[raw_index << 1 | 0] = pos
                has_name.append(keep)
                has_data.append(keep)
                has_reverse.append(False)
            raw_index += 1
        return raw_index, total_len

    def _held_read_tables(self, read_self_t) -> Optional[dict]:
        """Where the job's read-side seed table is kept, for the
        overlapper to build into and take from — only while the reads
        it is handed ARE the read set's (a read named like a target is
        mapped as that target's bytes, which change with every
        round)."""
        if self._reads is None or (read_self_t >= 0).any():
            return None
        return self._reads.seed_tables

    def _generate_overlaps(self, raw_index: int,
                           name_to_id: Dict[bytes, int],
                           id_to_id: Dict[int, int]) -> List[Overlap]:
        """``--overlaps auto``: run the first-party overlapper
        (:mod:`racon_tpu.ops.overlap_seed` + :mod:`racon_tpu.ops.chain`)
        over the already-loaded pools and emit transmuted ``Overlap``
        rows — downstream (filter, breaking points, windows) is exactly
        the PAF path over the same rows."""
        from ..ops import chain as chain_ops
        from ..ops import overlap_seed
        metrics.set_gauge("overlap.mode_auto", 1)
        metrics.inc("overlap.queries", raw_index)
        read_pos = [id_to_id[i << 1] for i in range(raw_index)]
        read_seqs = [self.sequences[p].data for p in read_pos]
        target_seqs = [self.sequences[i].data
                       for i in range(self.targets_size)]
        read_self_t = np.fromiter(
            (p if p < self.targets_size else -1 for p in read_pos),
            np.int64, raw_index)
        k = max(4, min(16, flags.get_int("RACON_TPU_OVERLAP_K")))
        # race the chain-arena compile against host seeding/matching
        est_len = max((len(s) for s in read_seqs), default=0)
        overlap_seed.warmup_async(est_len, len(read_seqs))
        chain_ops.warmup_async(_est_chain_seeds(est_len), raw_index, k=k)
        # graftlint: disable=jit-shape-hazard (k is a run-constant flag value clipped to 4..16 — one compile per run)
        rows = chain_ops.find_overlaps(
            read_seqs, target_seqs, read_self_t, k=k,
            read_tables=self._held_read_tables(read_self_t))
        overlaps: List[Overlap] = []
        for i in range(rows["q_ord"].size):
            q = int(rows["q_ord"][i])
            t = int(rows["t_idx"][i])
            o = Overlap.from_paf(
                self.sequences[read_pos[q]].name, len(read_seqs[q]),
                int(rows["q_begin"][i]), int(rows["q_end"][i]),
                "-" if int(rows["strand"][i]) else "+",
                self.sequences[t].name, len(target_seqs[t]),
                int(rows["t_begin"][i]), int(rows["t_end"][i]))
            o.transmute(self.sequences, name_to_id, id_to_id)
            if o.is_valid:
                overlaps.append(o)
        self.logger.log("[racon_tpu::Polisher::initialize] generated "
                        "overlaps (first-party overlapper)")
        return overlaps

    def _kick_consensus_warmup(self, est_pairs: int,
                               longest_overlap: int = 0) -> None:
        """Background warm-up compilation of the consensus refinement
        loop from the overlap/target histograms: the first consensus
        compile (~16 s) then hides inside the device overlap alignment
        instead of stalling polish(). Skipped for tiny inputs (the
        compile would outlive the whole run) and for engines that
        offer no warm-up; a wrong shape estimate only wastes a
        background compile (see TpuPoaConsensus.warmup_async).
        ``longest_overlap`` (where the overlaps are known) bounds a
        layer from above: no layer of a 150-base read set fills a
        window, and the engine's sweep and vote widths follow the
        layers it is given, so the warm-up has to be told."""
        warm = getattr(self.consensus, "warmup_async", None)
        if warm is None:
            return
        targets_bases = sum(len(self.sequences[i].data)
                            for i in range(self.targets_size))
        est_windows = targets_bases // self.window_length + \
            self.targets_size
        if est_pairs >= WARMUP_MIN_PAIRS:
            warm(self.window_length, est_pairs, est_windows,
                 est_layer_len=min(longest_overlap,
                                   self.window_length + 64),
                 est_contigs=self.targets_size)

    def _transmute_all(self, has_name, has_data, has_reverse) -> None:
        """transmute-parallelism (reference P3: one future per sequence,
        ``polisher.cpp:368-377``): revcomp materialization is a numpy
        LUT-take + flip (``sequence.py``), which releases the GIL on
        real read lengths, so a thread pool parallelizes it — one task
        per contiguous slice of the read set, never one per read (a
        future costs more than most transmutes), and no pool at all for
        a short-read set (NGS: mean read length <= 1000): a 150-base
        transmute never lets go of the interpreter lock, and eight
        threads passing it round took 16 s (a task a read) and 5 s
        (sliced) on the chip host for the 0.6 s of work in 345,000
        reads."""
        seqs = self.sequences

        def work(lo: int, hi: int) -> None:
            for i in range(lo, hi):
                seqs[i].transmute(has_name[i], has_data[i], has_reverse[i])

        with obs.span("transmute"):
            if (self.num_threads > 1 and len(seqs) > 64
                    and self._window_type is WindowType.TGS):
                from concurrent.futures import ThreadPoolExecutor
                step = -(-len(seqs) // (4 * self.num_threads))
                with ThreadPoolExecutor(self.num_threads) as pool:
                    for f in [pool.submit(work, lo,
                                          min(lo + step, len(seqs)))
                              for lo in range(0, len(seqs), step)]:
                        f.result()
            else:
                work(0, len(seqs))

    def _generate_overlaps_stream(self, raw_index: int,
                                  name_to_id: Dict[bytes, int],
                                  id_to_id: Dict[int, int],
                                  has_name, has_data, has_reverse,
                                  t_parse: float) -> List[Overlap]:
        """``--overlaps auto`` under ``RACON_TPU_OVERLAP_RAGGED``: the
        streamed overlap→align hand-off. The order of events: seeding
        and the join are a barrier; the chain stream then plans its
        chunks once and launches them, and each fetched chunk's
        completed query groups arrive here as one block of canonical
        rows (:func:`racon_tpu.ops.chain.iter_overlap_groups`).
        ``batches()`` walks a block row by row (span ``overlap.rows``:
        the ``Overlap`` objects, exactly the :meth:`_filter_overlaps`
        consecutive-run sweep as each query's run completes, the early
        reverse complements) and hands the align session a batch
        whenever :data:`STREAM_FEED_OVERLAPS` kept overlaps are
        collected at the end of a query's rows. The chain chunks still
        in flight run under the aligner's first packs; a job is a
        handful of chunks, so most rows arrive with the last fetches
        (gauge ``overlap.first_emit_pairs``). Kept overlaps accumulate
        in feed order, which IS the barrier path's order (the canonical
        row sort's primary key is the query ordinal), so the polished
        output stays byte-identical to the phase-barriered path."""
        from ..ops import chain as chain_ops
        from ..ops import overlap_seed
        metrics.set_gauge("overlap.mode_auto", 1)
        metrics.set_gauge("overlap.streamed", 1)
        metrics.inc("overlap.queries", raw_index)
        read_pos = [id_to_id[i << 1] for i in range(raw_index)]
        read_seqs = [self.sequences[p].data for p in read_pos]
        target_seqs = [self.sequences[i].data
                       for i in range(self.targets_size)]
        read_self_t = np.fromiter(
            (p if p < self.targets_size else -1 for p in read_pos),
            np.int64, raw_index)
        k = max(4, min(16, flags.get_int("RACON_TPU_OVERLAP_K")))
        # race the chain-arena compile against host seeding/matching
        est_len = max((len(s) for s in read_seqs), default=0)
        overlap_seed.warmup_async(est_len, len(read_seqs))
        chain_ops.warmup_async(_est_chain_seeds(est_len), raw_index, k=k)

        state = {"est_pairs": 0}

        def flush_run(run: List[Overlap]) -> List[Overlap]:
            # one consecutive same-q_id run through the
            # _filter_overlaps sweep (error/self drop; C mode keeps the
            # longest, later overlap winning ties)
            kept = [o for o in run
                    if o.error <= self.error_threshold
                    and o.q_id != o.t_id]
            if kept and self.type == PolisherType.C:
                best = kept[0]
                for o in kept[1:]:
                    if o.length >= best.length:
                        best = o
                kept = [best]
            # a run is one query's rows (the stream emits per query)
            metrics.inc("overlap.queries_kept", int(bool(kept)))
            for o in kept:
                if o.strand:
                    has_reverse[o.q_id] = True
                    # align reads the revcomp span before the deferred
                    # full transmute runs — materialize it at flush
                    # (idempotent; the transmute pass reuses it)
                    self.sequences[o.q_id].create_reverse_complement()
                else:
                    has_data[o.q_id] = True
                state["est_pairs"] += o.length // self.window_length + 1
            return kept

        buf: List[Overlap] = []
        run: List[Overlap] = []

        def take(cols: List[list], i: int) -> int:
            # rows i.. of one block into ``run`` / ``buf``, up to the
            # end of the first query after which ``buf`` holds a batch
            # (or the block's end); returns the next row
            q_ord = cols[0]
            n = len(q_ord)
            while i < n:
                q, t, strand, qb, qe, tb, te = (c[i] for c in cols)
                i += 1
                o = Overlap.from_paf(
                    self.sequences[read_pos[q]].name, len(read_seqs[q]),
                    qb, qe, "-" if strand else "+",
                    self.sequences[t].name, len(target_seqs[t]), tb, te)
                o.transmute(self.sequences, name_to_id, id_to_id)
                if o.is_valid:
                    if run and o.q_id != run[-1].q_id:
                        buf.extend(flush_run(run))
                        run.clear()
                    run.append(o)
                if (len(buf) >= STREAM_FEED_OVERLAPS
                        and (i == n or q_ord[i] != q)):
                    break
            return i

        def batches():
            nonlocal buf
            with obs.span("overlap.filter"):
                pass  # span parity with the barrier path (work is inline)
            # graftlint: disable=jit-shape-hazard (k is a run-constant flag value clipped to 4..16 — one compile per run)
            for rows in chain_ops.iter_overlap_groups(
                    read_seqs, target_seqs, read_self_t, k=k,
                    read_tables=self._held_read_tables(read_self_t)):
                cols = [rows[key].tolist() for key in (
                    "q_ord", "t_idx", "strand", "q_begin", "q_end",
                    "t_begin", "t_end")]
                i = 0
                while i < len(cols[0]):
                    # closed before the yield: the consumer's seconds
                    # are not this span's
                    with obs.span("overlap.rows"):
                        i = take(cols, i)
                    if len(buf) >= STREAM_FEED_OVERLAPS:
                        yield buf
                        buf = []
            with obs.span("overlap.rows"):
                buf.extend(flush_run(run))
            # every overlap is known now but alignment is still
            # draining — the consensus compile hides under it exactly
            # like the barrier path's placement before align
            self._kick_consensus_warmup(state["est_pairs"])
            if buf:
                yield buf

        overlaps: List[Overlap] = []
        # graftlint: disable=lock-discipline (one builder thread per polisher; see _initialize_core)
        self.timings["parse_s"] = round(time.perf_counter() - t_parse, 3)
        self.find_overlap_breaking_points(overlaps, feed=batches())
        if not overlaps:
            raise ValueError("empty overlap set")
        self.logger.log("[racon_tpu::Polisher::initialize] generated "
                        "overlaps (first-party overlapper, streamed)")
        self.logger.log()
        self._transmute_all(has_name, has_data, has_reverse)
        return overlaps

    def _filter_overlaps(self, overlaps: List[Overlap]) -> List[Overlap]:
        """Per-query group filter (``polisher.cpp:283-307``): drop
        error > threshold and self overlaps; for contig polishing keep only
        the longest overlap per consecutive same-query group (the later
        overlap wins length ties, matching the reference's pairwise sweep)."""
        result: List[Overlap] = []
        i = 0
        while i < len(overlaps):
            j = i
            while j < len(overlaps) and overlaps[j].q_id == overlaps[i].q_id:
                j += 1
            group = [o for o in overlaps[i:j]
                     if o.error <= self.error_threshold and o.q_id != o.t_id]
            if group and self.type == PolisherType.C:
                best = group[0]
                for o in group[1:]:
                    if o.length >= best.length:
                        best = o
                group = [best]
            result.extend(group)
            i = j
        return result

    def find_overlap_breaking_points(self, overlaps: List[Overlap],
                                     feed=None) -> None:
        """Align CIGAR-less overlaps (batched through the aligner backend —
        reference: ``polisher.cpp:461-483`` / ``cudapolisher.cpp:86-200``)
        then derive per-window breaking points, advancing the reference's
        20-bin progress bar (``polisher.cpp:475-481``). Host-side CIGARs
        (SAM input, host aligner output) decode to columnar rows in one
        native thread-pool batch instead of per-overlap Python walks.

        ``feed`` (the streaming overlap→align handoff) is an iterator of
        filtered, transmuted ``Overlap`` batches still being produced by
        the chain stream: each batch is appended to ``overlaps`` and fed
        to the align session as it arrives, so overlap generation for
        later query groups runs under the alignment of earlier ones. A
        backend without a streaming session drains the feed first and
        takes the barrier path — same bytes either way."""
        log = self.logger
        t_align = time.perf_counter()
        msg = "[racon_tpu::Polisher::initialize] aligning overlaps"
        if feed is not None and not (
                getattr(self.aligner, "wants_full_stream", False)
                and getattr(self.aligner, "bp_stream", None) is not None):
            # host/sessionless aligner: nothing to pipeline into — drain
            # the producer, then run the phase exactly as barriered
            for batch in feed:
                overlaps.extend(batch)
            feed = None
        need = [o for o in overlaps
                if not o.cigar and o.breaking_points is None]
        # dispatch-vs-fetch attribution (round 17): the round-11 span
        # timers already measure both halves — snapshot them around the
        # phase so pipeline_init_breakdown can say whether the 85s of
        # align_s is host packing/dispatch or blocking device fetches.
        # Read THIS THREAD's mirror when one is armed (chip workers set
        # a device.<ordinal>. timer prefix): the unprefixed timers are
        # process-global, so concurrent chip workers' spans would
        # cross-contaminate each shard's reported split
        from ..obs import trace as obs_trace
        scope = ((metrics.get_scope() or "")
                 + (obs_trace.get_timer_prefix() or ""))
        t_disp0 = metrics.timer_s(scope + "align.dispatch")
        t_fetch0 = metrics.timer_s(scope + "align.fetch")
        # sanitizer: the overlap-alignment phase compiles one kernel set
        # per (bucket, batch) shape — a per-chunk recompile is a
        # regression this budget catches (no-op unless RACON_TPU_SANITIZE).
        # Scoped to the aligner kernel modules so the background
        # consensus warm-up thread's compiles are not charged here.
        with obs.span("align", pairs=len(need)), \
                sanitize.PhaseRetraceBudget(
                    "align", prefixes=("racon_tpu.ops.nw",
                                       "racon_tpu.ops.pallas_nw",
                                       "racon_tpu.parallel")):
            if feed is not None:
                self._align_feed(feed, overlaps, need, log, msg)
            else:
                self._align_need(need, log, msg)
        self.timings["align_s"] = round(time.perf_counter() - t_align, 3)
        self.timings["align_dispatch_s"] = round(
            metrics.timer_s(scope + "align.dispatch") - t_disp0, 3)
        self.timings["align_fetch_s"] = round(
            metrics.timer_s(scope + "align.fetch") - t_fetch0, 3)

        t_decode = time.perf_counter()
        # the span covers the whole host decode phase — zero-length on
        # the device path, where breaking points came off the chip as
        # columnar rows inside align.fetch
        with obs.span("bp.decode"):
            todo = [o for o in overlaps if o.breaking_points is None]
            if todo:
                arrs = decode_breaking_points_batch(
                    [o.cigar or "" for o in todo],
                    [o.q_length - o.q_end if o.strand else o.q_begin
                     for o in todo],
                    [o.t_begin for o in todo], [o.t_end for o in todo],
                    self.window_length, self.num_threads)
                for o, arr in zip(todo, arrs):
                    o.breaking_points = arr
                    o.cigar = None
        self.timings["bp_decode_s"] = round(
            time.perf_counter() - t_decode, 3)
        self.logger.log("[racon_tpu::Polisher::initialize] aligned overlaps")

    def _align_feed(self, feed, overlaps, need, log, msg) -> None:
        """The consuming half of the overlap→align hand-off: take
        filtered overlap batches off ``feed`` (the generator of
        :meth:`_generate_overlaps_stream`, which runs HERE, on this
        thread, inside span ``align`` between two ``sess.feed`` calls:
        its host work is ``align``'s self time but for the chain
        launches and fetches, and the timer-only leaves
        ``overlap.chain.plan`` / ``.emit`` / ``.rows`` say how much) and
        feed the round-17 align session as they arrive. The session
        packs and dispatches asynchronously, so the aligner's first
        chunks run while the generator fetches the last chain chunks
        and builds the later batches; ``overlap_feed_s`` records the
        producer wall inside the phase.

        ``bp_stream`` can return None even on a streaming-capable
        backend (mesh runs) — then there
        is no session to pipeline into, so drain the producer and take
        the barrier path, same as a sessionless backend."""
        sess = self.aligner.bp_stream(
            self.window_length, total=len(need),
            progress=lambda d, t: log.bar_to(msg, d, t))
        feed_wall = 0.0
        t0 = time.perf_counter()
        for batch in feed:
            feed_wall += time.perf_counter() - t0
            overlaps.extend(batch)
            part = [o for o in batch
                    if not o.cigar and o.breaking_points is None]
            if part:
                need.extend(part)
                if sess is not None:
                    pairs = [(o.query_span_bytes(self.sequences),
                              o.target_span_bytes(self.sequences))
                             for o in part]
                    metas = [(o.t_begin,
                              o.q_length - o.q_end if o.strand
                              else o.q_begin)
                             for o in part]
                    sess.feed(pairs, metas, [o.error for o in part])
            t0 = time.perf_counter()
        if sess is not None:
            for o, bp in zip(need, sess.finish()):
                o.breaking_points = bp
        else:
            self._align_need(need, log, msg)
        # graftlint: disable=lock-discipline (one builder thread per polisher; see _initialize_core)
        self.timings["overlap_feed_s"] = round(feed_wall, 3)
        metrics.add_time("overlap.stream_feed", feed_wall)

    def _align_need(self, need, log, msg) -> None:
        """The backend-dispatch half of breaking-point alignment (split
        out so the sanitizer's retrace budget wraps exactly the phase
        that launches kernels)."""
        if getattr(self.aligner, "wants_full_stream", False):
            # device backend buckets/chunks internally; hand it a large
            # slice so batches stay dense, but still bound the transient
            # span copies (2x aligned bases of duplicated host bytes if
            # unbounded — reference analog: 1 GiB streaming chunks,
            # polisher.cpp:26). Breaking points come straight off the
            # device as columnar rows (~8 bytes per window boundary)
            # instead of CIGARs (~2 bits per base) — the host link's
            # bandwidth, not the DP, bounded the aligner.
            chunk = 65536
            # ragged align stream (round 17): the slices FEED one
            # session, so packing/dispatch/fetch pipeline across slice
            # boundaries (the per-slice drain used to idle the device
            # at every 64k boundary) and each pair's band seeds from
            # its overlap's filter-time error estimate
            mk = getattr(self.aligner, "bp_stream", None)
            sess = mk(self.window_length, total=len(need),
                      progress=lambda d, t: log.bar_to(msg, d, t)) \
                if mk is not None else None
            for begin in range(0, len(need), chunk):
                part = need[begin:begin + chunk]
                pairs = [(o.query_span_bytes(self.sequences),
                          o.target_span_bytes(self.sequences)) for o in part]
                metas = [(o.t_begin,
                          o.q_length - o.q_end if o.strand else o.q_begin)
                         for o in part]
                errs = [o.error for o in part]
                if sess is not None:
                    sess.feed(pairs, metas, errs)
                    continue
                base = begin
                bps = self.aligner.breaking_points_batch(
                    pairs, metas, self.window_length,
                    progress=lambda d, t: log.bar_to(msg, base + d,
                                                     len(need)),
                    errors=errs)
                for o, bp in zip(part, bps):
                    o.breaking_points = bp
            if sess is not None:
                for o, bp in zip(need, sess.finish()):
                    o.breaking_points = bp
        else:
            # host path: bounded chunks keep transient span copies O(chunk)
            # rather than O(total reads) (reference analog: 1 GiB streaming
            # chunks, polisher.cpp:26)
            chunk = 1024
            for begin in range(0, len(need), chunk):
                part = need[begin:begin + chunk]
                pairs = [(o.query_span_bytes(self.sequences),
                          o.target_span_bytes(self.sequences)) for o in part]
                cigars = self.aligner.align_batch(pairs)
                for o, cigar in zip(part, cigars):
                    o.cigar = cigar
                log.bar_to(msg, begin + len(part), len(need))

    # ------------------------------------------------------- window build

    def _build_backbone_windows(self) -> None:
        """Slice every target into backbone windows (layer 0). Records the
        per-target first-window offsets and per-window backbone lengths
        the vectorized layer assembly indexes into."""
        window_length = self.window_length
        id_to_first = np.zeros(self.targets_size + 1, dtype=np.int64)
        win_lens: List[int] = []
        for i in range(self.targets_size):
            target = self.sequences[i]
            data = target.data
            quality = target.quality
            k = 0
            for j in range(0, len(data), window_length):
                length = min(j + window_length, len(data)) - j
                q = (self._dummy_quality[:length] if quality is None
                     else quality[j:j + length])
                self.windows.append(Window(i, k, self._window_type,
                                           data[j:j + length], q))
                win_lens.append(length)
                k += 1
            id_to_first[i + 1] = id_to_first[i] + k
        # graftlint: disable=lock-discipline (one builder thread per polisher; see _initialize_core)
        self._id_to_first_window = id_to_first
        # graftlint: disable=lock-discipline (one builder thread per polisher; see _initialize_core)
        self._window_lengths = np.asarray(win_lens, dtype=np.int64)

    def _layer_refs(self, overlaps: List[Overlap]):
        """Per-overlap oriented (data, quality) references into the read
        set — forward or reverse-complement per strand."""
        data_refs: List[bytes] = []
        qual_refs: List[Optional[bytes]] = []
        for o in overlaps:
            seq = self.sequences[o.q_id]
            if o.strand:
                data_refs.append(seq.reverse_complement)
                qual_refs.append(seq.reverse_quality)
            else:
                data_refs.append(seq.data)
                qual_refs.append(seq.quality)
        return data_refs, qual_refs

    def _prepare_layers(self, overlaps: List[Overlap]) -> PreparedPool:
        """The half of the layer assembly that reads no breaking point:
        the read pool over every overlap and the quality prefix sums
        (:meth:`LayerStore.prepare`). ONE function with two call times:
        beside the aligner where :meth:`_initialize_core` could start it
        ahead, else inline in :meth:`_assemble_layers`."""
        with obs.span("build.prepare", overlaps=len(overlaps)):
            prep = LayerStore.prepare(*self._layer_refs(overlaps))
        metrics.inc("build.pool_bytes", int(prep.pool.nbytes))
        return prep

    def _start_prepare(self, overlaps: List[Overlap]) -> None:
        """Run :meth:`_prepare_layers` on a thread of its own;
        :meth:`_assemble_layers` joins it (or :meth:`_drop_prepare`)."""
        task = _PrepareAhead(overlaps)
        # the metrics scope is thread-local: re-declare the caller's, as
        # run()'s producer does
        job_scope = metrics.get_scope()

        def work():
            metrics.set_scope(job_scope)
            try:
                task.result = self._prepare_layers(overlaps)
            # graftlint: disable=swallowed-exception (re-raised on the thread that joins)
            except BaseException as e:
                task.error = e

        task.thread = threading.Thread(target=work, name="racon-prepare",
                                       daemon=True)
        task.thread.start()
        # graftlint: disable=lock-discipline (written before the builder exists, taken by the one builder; see _initialize_core)
        self._prepare_ahead = task

    def _drop_prepare(self, wait: bool) -> None:
        """Forget a prepare started ahead (the run failed before its
        barrier); ``wait`` retires its thread first."""
        # graftlint: disable=lock-discipline (one builder thread per polisher; see _initialize_core)
        task, self._prepare_ahead = self._prepare_ahead, None
        if task is not None and wait:
            task.thread.join()

    def _take_prepared(self, overlaps: List[Overlap]) -> PreparedPool:
        """The barrier between the two halves: the prepare started
        ahead for ``overlaps`` (waited for if unfinished, its exception
        re-raised here), else the same call inline.
        ``build.pool_bytes_ahead`` counts the pool bytes that were ready
        before anything had to wait for them."""
        # graftlint: disable=lock-discipline (one builder thread per polisher; see _initialize_core)
        task, self._prepare_ahead = self._prepare_ahead, None
        if task is None or task.overlaps is not overlaps:
            metrics.inc("build.pool_bytes_ahead", 0)
            return self._prepare_layers(overlaps)
        done = not task.thread.is_alive()
        with obs.span("build.prepare_wait"):
            task.thread.join()
        if task.error is not None:
            raise task.error
        metrics.inc("build.pool_bytes_ahead",
                    int(task.result.pool.nbytes) if done else 0)
        return task.result

    def _filter_layer_rows(self, prep: PreparedPool, bp, pair_ov, t_ids):
        """The vectorized filter core of :meth:`_assemble_layers` —
        min-span, mean-PHRED and window arithmetic over one concatenated
        (P, 4) breaking-point matrix, whichever aligner wrote its rows
        (device tables, host fallback, a file's CIGARs). Returns
        ``(keep, win_id, layer_begin, layer_end)`` aligned with ``bp``'s
        rows."""
        window_length = self.window_length
        t_first, q_first = bp[:, 0], bp[:, 1]
        t_endx, q_endx = bp[:, 2], bp[:, 3]
        span = q_endx - q_first

        # min-span filter: same float compare as the legacy per-pair loop
        keep = ~(span < 0.02 * window_length)

        # mean-PHRED filter as a lookup into the prepared prefix sums
        # over the quality pool (each overlap's read at ov_off): integer
        # sums are exact in float64, so sums/span - 33.0 reproduces the
        # legacy  qual[b:e].mean() - 33.0  bit-for-bit
        sel = np.flatnonzero(prep.hq_ov[pair_ov])
        if sel.size:
            base = prep.ov_off[pair_ov[sel]]
            sums = (prep.qsum[base + q_endx[sel]]
                    - prep.qsum[base + q_first[sel]]).astype(np.int64)
            keep[sel] &= ((sums / span[sel] - 33.0)
                          >= self.quality_threshold)

        rank = t_first // window_length
        win_id = self._id_to_first_window[t_ids[pair_ov]] + rank
        layer_begin = t_first - rank * window_length
        layer_end = t_endx - rank * window_length - 1
        # add_layer's begin == end silent skip, vectorized
        keep &= layer_begin != layer_end
        return keep, win_id, layer_begin, layer_end

    def _assemble_layers(self, overlaps: List[Overlap], emit=None,
                         chunk_windows: int = 0) -> None:
        """Columnar layer assembly, the half that needs breaking points
        (the other half, :meth:`_prepare_layers`, is taken at the
        barrier below: joined if it ran beside the aligner, else run
        here): one concatenated (P, 4) breaking-point matrix, vectorized
        min-span/mean-PHRED filters and window arithmetic, a single
        stable argsort grouping layers by window, and a tight attach
        loop over the covered windows.

        ``emit(first_window, end_window)`` (optional) is called after
        every ``chunk_windows``-sized window range has all its layers —
        the :meth:`run` producer streams those ranges into the consensus
        queue. Emission walks window ranks in order, so a range is
        complete exactly when the sorted pair sweep passes it."""
        t_build = time.perf_counter()
        if self._id_to_first_window is None:
            self._build_backbone_windows()
        try:
            prep = self._take_prepared(overlaps)
        except BaseException:
            # as a failed alignment leaves it: a retry re-initializes
            # graftlint: disable=lock-discipline (one builder thread per polisher; see _initialize_core)
            self.windows = []
            raise
        window_length = self.window_length
        n_ov = len(overlaps)
        n_win = len(self.windows)
        t_ids = np.fromiter((o.t_id for o in overlaps), np.int64, n_ov)
        # graftlint: disable=lock-discipline (one builder thread per polisher; see _initialize_core)
        self.targets_coverages = np.bincount(
            t_ids, minlength=self.targets_size).tolist()

        counts = np.fromiter(
            (0 if o.breaking_points is None else len(o.breaking_points)
             for o in overlaps), np.int64, n_ov)
        total_pairs = int(counts.sum())
        if total_pairs == 0:
            if emit is not None:
                emit(0, n_win)
            self.timings["layer_append_s"] = 0.0
            self.timings["layer_store_s"] = 0.0
            self.timings["build_windows_s"] = round(
                self._backbone_s + (time.perf_counter() - t_build), 3)
            return
        bp = np.concatenate(
            [o.breaking_points for o in overlaps
             if o.breaking_points is not None
             and len(o.breaking_points)]).astype(np.int64)
        pair_ov = np.repeat(np.arange(n_ov), counts)
        q_first, q_endx = bp[:, 1], bp[:, 3]
        keep, win_id, layer_begin, layer_end = self._filter_layer_rows(
            prep, bp, pair_ov, t_ids)
        prep.qsum = None

        kept = np.flatnonzero(keep)
        if kept.size:
            backbone_len = self._window_lengths[win_id[kept]]
            if ((layer_begin[kept] > layer_end[kept])
                    | (layer_end[kept] > backbone_len)).any():
                raise ValueError("layer begin and end positions are invalid")

        # window-major grouping: stable, so layers keep the overlap-stream
        # order inside each window (the POA's tie-break contract)
        order = kept[np.argsort(win_id[kept], kind="stable")]
        sorted_win = win_id[order]

        windows = self.windows
        if not chunk_windows:
            chunk_windows = n_win
        # columnar layer storage (round 10): ONE deduplicated read pool
        # (prepared above or ahead) plus flat (offset, len, begin, end)
        # rows replace the per-layer slice-and-append loop that used to
        # dominate init CPU (layer_append_s); windows get an O(1) lazy
        # view and the device packers gather their lane blocks straight
        # from the pool
        t_store = time.thread_time()
        with obs.span("build.store", rows=int(order.size)):
            store = LayerStore.build(
                prep, pair_ov[order], q_first[order], q_endx[order],
                sorted_win, layer_begin[order], layer_end[order], n_win)
        self.timings["layer_store_s"] = round(
            time.thread_time() - t_store, 3)
        t_append = time.thread_time()
        bounds = store.row_bounds
        # attach chunk-by-chunk and emit each range the moment its
        # windows have their layers: consumers without a stream()
        # session (CPU/native engines, mesh runs) start polishing the
        # first range while later ranges are still attaching — the
        # round-7 init->polish overlap contract survives the columnar
        # store (the breaking-point filter and sort above are the only
        # remaining pre-emission serial section once the pool was
        # prepared ahead). thread_time keeps a blocking
        # emit (bounded queue put) out of the append accounting.
        for w0 in range(0, n_win, chunk_windows):
            w1 = min(w0 + chunk_windows, n_win)
            for wi in range(w0, w1):
                r0, r1 = int(bounds[wi]), int(bounds[wi + 1])
                if r1 > r0:
                    windows[wi].attach_layers(store, r0, r1)
            if emit is not None:
                emit(w0, w1)
        # the attach loop is all that remains of the per-layer append
        # cost (the run report's ``layer_append_s`` phase)
        self.timings["layer_append_s"] = round(
            time.thread_time() - t_append, 3)

        for o in overlaps:
            o.breaking_points = None
        if self.evict_reads:
            # the layer store pooled a copy of every referenced read
            # orientation above, so the original read payloads
            # (data + revcomp + qualities) are dead weight from here
            # on — the shard runner's memory budget counts on this
            for seq in self.sequences[self.targets_size:]:
                seq.release()
        self.timings["build_windows_s"] = round(
            self._backbone_s + (time.perf_counter() - t_build), 3)

    def _build_windows_legacy(self, overlaps: List[Overlap]) -> None:
        """The pre-columnar per-overlap/per-pair build, kept verbatim (on
        the row representation) as the parity oracle for
        ``tests/test_columnar_init.py``. Not called by the pipeline."""
        window_length = self.window_length
        if self._id_to_first_window is None:
            self._build_backbone_windows()
        id_to_first_window = self._id_to_first_window

        self.targets_coverages = [0] * self.targets_size

        min_span = 0.02 * window_length
        for o in overlaps:
            self.targets_coverages[o.t_id] += 1
            seq = self.sequences[o.q_id]
            bp = o.breaking_points
            data_all = seq.reverse_complement if o.strand else seq.data
            qual_all = seq.reverse_quality if o.strand else seq.quality
            qual_arr = (np.frombuffer(qual_all, dtype=np.uint8)
                        if qual_all else None)
            for row in (bp if bp is not None else ()):
                t_begin, q_begin = int(row[0]), int(row[1])
                t_end, q_end = int(row[2]), int(row[3])
                if q_end - q_begin < min_span:
                    continue
                if qual_arr is not None:
                    avg = float(qual_arr[q_begin:q_end].mean()) - 33.0
                    if avg < self.quality_threshold:
                        continue
                window_rank = t_begin // window_length
                window_id = int(id_to_first_window[o.t_id]) + window_rank
                window_start = window_rank * window_length
                data = data_all[q_begin:q_end]
                quality = (qual_all[q_begin:q_end]
                           if qual_all is not None else None)
                self.windows[window_id].add_layer(
                    data, quality,
                    t_begin - window_start,
                    t_end - window_start - 1)
            o.breaking_points = None

    # -------------------------------------------------------------- polish

    def polish(self, drop_unpolished_sequences: bool = True) -> List[Sequence]:
        log = self.logger
        log.log()

        msg = "[racon_tpu::Polisher::polish] generating consensus"
        with obs.span("consensus", windows=len(self.windows)), \
                sanitize.PhaseRetraceBudget(
                    "consensus", prefixes=("racon_tpu.ops.poa",
                                           "racon_tpu.ops.pallas_nw",
                                           "racon_tpu.parallel")):
            polished_flags = self.consensus.run(
                self.windows, self.trim,
                progress=lambda d, t: log.bar_to(msg, d, t))
        with obs.span("stitch"):
            return self._stitch(polished_flags, drop_unpolished_sequences)

    def run(self, drop_unpolished_sequences: bool = True) -> List[Sequence]:
        """Fused initialize + polish with the two phases pipelined: the
        columnar layer assembly streams completed window ranges through a
        bounded queue into the consensus engine, so polishing starts on
        fully-layered windows while later windows are still being built
        (on top of the intra-init overlaps ``_initialize_core`` already
        runs). ``num_threads == 1`` — and an already-initialized polisher
        — take the sequential initialize()/polish() path; output is
        byte-identical either way (per-window consensus is independent of
        batch composition)."""
        if self.windows:
            return self.polish(drop_unpolished_sequences)
        if self.num_threads <= 1:
            self.initialize()
            return self.polish(drop_unpolished_sequences)

        from queue import Queue

        overlaps = self._initialize_core()
        log = self.logger
        log.log()

        n_win = len(self.windows)
        # granularity: about one consensus device group's worth of layer
        # pairs per range (group_pairs_hint — keeps the engine's fused
        # executions full-size), never below 1024 windows
        rows = sum(0 if o.breaking_points is None
                   else len(o.breaking_points) for o in overlaps)
        depth = max(1.0, rows / max(1, n_win))
        chunk_windows = max(
            1024, int(getattr(self.consensus, "group_pairs_hint", 32768)
                      / depth))
        ranges: "Queue" = Queue(maxsize=4)  # bounded in-flight depth
        failure: List[BaseException] = []
        # sanitizer: stall monitor over the bounded queue — a deadlocked
        # producer/consumer pair dumps all thread stacks (first
        # timeout), then fails the run with a stall-class fault (second
        # timeout) so the shard runner's ladder can retry/quarantine the
        # shard instead of hanging forever (None unless
        # RACON_TPU_SANITIZE=1). A consumer wedged inside device
        # compute cannot be unblocked from in-process — the lease TTL
        # covers that across workers; this escalation covers the wedged
        # producer / deadlocked-queue shapes.
        stall_mark = object()

        def escalate():
            failure.append(faults.StallError(
                "init->polish queue made no progress past the "
                "escalation timeout — failing the attempt with a "
                "stall-class fault"))
            from queue import Empty, Full
            try:  # unblock a producer waiting on a full queue
                ranges.get_nowait()
            except Empty:  # graftlint: disable=swallowed-exception (best-effort unblock)
                pass
            try:  # unblock a consumer waiting on an empty queue
                ranges.put_nowait(stall_mark)
            except Full:  # graftlint: disable=swallowed-exception (best-effort unblock)
                pass

        watchdog = sanitize.queue_watchdog(
            "init->polish queue",
            escalate_cb=escalate if self.stall_escalation else None)

        def emit_range(a, b):
            if watchdog is not None:
                watchdog.beat()
            t_put = time.perf_counter()
            with obs.span("queue.put"):
                ranges.put((a, b))
            # registry: bounded-queue health for the heartbeat/report
            # (producer blocking time = init outrunning the consensus)
            metrics.add_time("queue.producer_wait_s",
                             time.perf_counter() - t_put)
            metrics.set_gauge("queue.depth", ranges.qsize())

        # job-scoped metrics (round 14): the scope is thread-local, so
        # the producer thread must re-declare the caller's — otherwise
        # a service job's queue/build telemetry would leak into the
        # global namespace and collide with concurrent jobs'
        job_scope = metrics.get_scope()

        def produce():
            metrics.set_scope(job_scope)
            try:
                t_cpu = time.thread_time()
                with obs.span("build.windows"):
                    self._assemble_layers(
                        overlaps, emit=emit_range,
                        chunk_windows=chunk_windows)
                # re-record with the producer's CPU time: its wall-clock
                # stretches under GIL sharing with the consensus engine,
                # which would overstate both the build cost and the
                # overlap saving derived from it
                self.timings["build_windows_s"] = round(
                    self._backbone_s + time.thread_time() - t_cpu, 3)
            # graftlint: disable=swallowed-exception (re-raised on the consumer thread)
            except BaseException as e:  # surfaced on the consumer side
                failure.append(e)
            finally:
                ranges.put(None)

        producer = threading.Thread(target=produce, name="racon-layers",
                                    daemon=True)
        producer.start()

        msg = "[racon_tpu::Polisher::polish] generating consensus"
        polished: List[bool] = [False] * n_win
        queue_wait = 0.0
        # double-buffered async dispatch (round 10): a ragged consensus
        # engine exposes a streaming session — each range is packed and
        # DISPATCHED as it arrives while earlier groups still compute on
        # device, and fetch/decode happens behind the in-flight budget
        # or at finish. Engines without a session (CPU backends, mesh
        # runs) keep the per-range blocking run() calls.
        stream_f = getattr(self.consensus, "stream", None)
        sess = None
        sess_tried = False
        fed_ranges: List = []
        try:
            with obs.span("consensus", windows=n_win), \
                    sanitize.PhaseRetraceBudget(
                        "consensus", prefixes=("racon_tpu.ops.poa",
                                               "racon_tpu.ops.pallas_nw",
                                               "racon_tpu.parallel")):
                while True:
                    t_get = time.perf_counter()
                    with obs.span("queue.get"):
                        item = ranges.get()
                    dt_get = time.perf_counter() - t_get
                    queue_wait += dt_get
                    metrics.add_time("queue.consumer_wait_s", dt_get)
                    metrics.set_gauge("queue.depth", ranges.qsize())
                    if watchdog is not None:
                        watchdog.beat()
                    if item is stall_mark:
                        raise (failure[0] if failure else
                               faults.StallError("init->polish queue "
                                                 "stall escalation"))
                    if item is None:
                        if failure and isinstance(failure[0],
                                                  faults.StallError):
                            raise failure[0]
                        break
                    a, b = item
                    if b > a:
                        if stream_f is not None and not sess_tried:
                            # session opens at the FIRST range: by then
                            # the layer store is fully built (ranges are
                            # emitted after the one-pass attach loop),
                            # so the live-window band hint below equals
                            # the padded path's batch-global maximum —
                            # the frozen band, and hence every byte of
                            # consensus, matches run() on the whole set
                            sess_tried = True
                            band_hint = max(
                                (len(w.backbone) for w in self.windows
                                 if w.layer_count >= 2), default=0)
                            sess = stream_f(trim=self.trim,
                                            band_hint=band_hint)
                        if sess is not None:
                            with obs.span("consensus.feed",
                                          windows=b - a):
                                sess.feed(self.windows[a:b])
                            fed_ranges.append((a, b))
                        else:
                            with obs.span("consensus.run",
                                          windows=b - a):
                                polished[a:b] = self.consensus.run(
                                    self.windows[a:b], self.trim)
                    log.bar_to(msg, b, n_win)
                if sess is not None:
                    with obs.span("consensus.finish"):
                        flags_all = sess.finish()
                    pos = 0
                    for a, b in fed_ranges:
                        polished[a:b] = flags_all[pos:pos + (b - a)]
                        pos += b - a
        except BaseException as e:
            # a stall escalation means the producer (or the queue) is
            # wedged: draining/joining would hang right back — abandon
            # the daemon thread and propagate so the ladder can degrade
            # the shard (a fresh attempt builds a fresh polisher; the
            # wedged thread touches only this object's state)
            if isinstance(e, faults.StallError):
                raise
            # a consensus fault mid-stream must not strand the producer
            # on the bounded queue: drain it and retire the thread
            # before propagating, else the daemon thread pins the whole
            # overlap/window state and keeps appending layers under any
            # later polish on this object. The drain is non-blocking:
            # the fault may fire AFTER the sentinel was consumed (e.g.
            # the retrace budget raising at the with-block exit), where
            # a blocking get() would deadlock on the empty queue.
            from queue import Empty
            while True:
                try:
                    if ranges.get_nowait() is None:
                        break
                except Empty:
                    if not producer.is_alive():
                        break
                    time.sleep(0.01)
            producer.join()
            raise
        finally:
            if watchdog is not None:
                watchdog.stop()
        producer.join()
        if failure:
            raise failure[0]
        # init->polish overlap actually realized: layer-assembly wall
        # that hid under the consensus engine instead of preceding it
        self.timings["pipeline_overlap_saved_s"] = round(
            max(0.0, self.timings.get("build_windows_s", 0.0)
                - queue_wait), 3)
        # the layer assembly finished no later than its last consumed
        # range; the log lands here so the two threads never interleave
        # writes inside the progress bar
        log.log("[racon_tpu::Polisher::initialize] "
                "transformed data into windows")
        with obs.span("stitch"):
            return self._stitch(polished, drop_unpolished_sequences)

    def _stitch(self, polished_flags: List[bool],
                drop_unpolished_sequences: bool) -> List[Sequence]:
        log = self.logger
        dst: List[Sequence] = []
        polished_data: List[bytes] = []
        num_polished = 0
        for i, window in enumerate(self.windows):
            num_polished += 1 if polished_flags[i] else 0
            polished_data.append(window.consensus)

            last = (i == len(self.windows) - 1 or
                    self.windows[i + 1].rank == 0)
            if last:
                ratio = num_polished / float(window.rank + 1)
                if not drop_unpolished_sequences or ratio > 0:
                    data = b"".join(polished_data)
                    tags = b"r" if self.type == PolisherType.F else b""
                    tags += b" LN:i:%d" % len(data)
                    tags += b" RC:i:%d" % self.targets_coverages[window.id]
                    tags += b" XC:f:%.6f" % ratio
                    dst.append(Sequence(
                        self.sequences[window.id].name + tags, data))
                num_polished = 0
                polished_data = []

        # the job that started a warm-up ends only when it has: the
        # wait is the last round's (a polisher with another one behind
        # it hands its engines on, warm-up and all; the shard runner's
        # slot waits once, when it has no shard left)
        drain = getattr(self.consensus, "drain_warmup", None)
        if drain is not None and self._final:
            drain()
        log.log("[racon_tpu::Polisher::polish] generated consensus")
        log.total("[racon_tpu::Polisher::] total =")
        self.windows = []
        self.sequences = []
        return dst
