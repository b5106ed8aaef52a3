#!/usr/bin/env python
"""Record device-engine goldens for every scenario the reference records
CUDA goldens for (test/racon_test.cpp:292-496): eight consensus runs
(incl. unit/e2e score sets and banded) + four fragment-correction runs, all through the accelerated engines
(consensus_backend="tpu"; -f also aligner_backend="tpu"). Prints one line
per scenario; values are bit-reproducible across the CPU-mesh XLA kernels
and the on-chip Pallas kernels, so tests assert them exactly.
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DATA = "/root/reference/test/data"


def rc_distance(polished):
    from racon_tpu.io import parse_fasta
    from racon_tpu import native
    ref = list(parse_fasta(f"{DATA}/sample_reference.fasta.gz"))[0]
    return native.edit_distance(polished.reverse_complement, ref.data)


def consensus(reads, overlaps, tag, **kw):
    from racon_tpu.core.polisher import create_polisher
    t0 = time.perf_counter()
    p = create_polisher(f"{DATA}/{reads}", f"{DATA}/{overlaps}",
                        f"{DATA}/sample_layout.fasta.gz", num_threads=8,
                        consensus_backend="tpu", **kw)
    p.initialize()
    (polished,) = p.polish(True)
    d = rc_distance(polished)
    stats = p.consensus.stats
    print(f"{tag}: rc={d} device_windows={stats['device_windows']} "
          f"fallback={stats['fallback_windows']} "
          f"({time.perf_counter() - t0:.0f}s)", flush=True)


def fragment(reads, overlaps, tag):
    from racon_tpu.core.polisher import PolisherType, create_polisher
    t0 = time.perf_counter()
    p = create_polisher(f"{DATA}/{reads}", f"{DATA}/{overlaps}",
                        f"{DATA}/{reads}", PolisherType.F,
                        window_length=500, quality_threshold=10.0,
                        error_threshold=0.3, match=1, mismatch=-1, gap=-1,
                        num_threads=8, consensus_backend="tpu",
                        aligner_backend="tpu")
    p.initialize()
    out = p.polish(False)
    total = sum(len(s.data) for s in out)
    stats = p.consensus.stats
    print(f"{tag}: n={len(out)} total={total} "
          f"device_windows={stats['device_windows']} "
          f"fallback={stats['fallback_windows']} "
          f"({time.perf_counter() - t0:.0f}s)", flush=True)


def fragment_kc(tag):
    from racon_tpu.core.polisher import PolisherType, create_polisher
    t0 = time.perf_counter()
    p = create_polisher(f"{DATA}/sample_reads.fastq.gz",
                        f"{DATA}/sample_ava_overlaps.paf.gz",
                        f"{DATA}/sample_reads.fastq.gz", PolisherType.C,
                        window_length=500, quality_threshold=10.0,
                        error_threshold=0.3, match=1, mismatch=-1, gap=-1,
                        num_threads=8, consensus_backend="tpu",
                        aligner_backend="tpu")
    p.initialize()
    out = p.polish(True)
    total = sum(len(s.data) for s in out)
    print(f"{tag}: n={len(out)} total={total} "
          f"device_windows={p.consensus.stats['device_windows']} "
          f"({time.perf_counter() - t0:.0f}s)", flush=True)


def main():
    if not os.path.isdir(DATA):
        sys.exit(f"record_goldens: the reference's λ-phage test data is "
                 f"not at {DATA}; this tool works only where that "
                 f"directory exists (the goldens are recorded against it)")
    import jax
    print(f"devices: {jax.devices()}", flush=True)
    consensus("sample_reads.fastq.gz", "sample_overlaps.paf.gz",
              "consensus_fastq_paf")
    consensus("sample_reads.fasta.gz", "sample_overlaps.paf.gz",
              "consensus_fasta_paf")
    consensus("sample_reads.fastq.gz", "sample_overlaps.sam.gz",
              "consensus_fastq_sam")
    consensus("sample_reads.fasta.gz", "sample_overlaps.sam.gz",
              "consensus_fasta_sam")
    consensus("sample_reads.fastq.gz", "sample_overlaps.paf.gz",
              "consensus_w1000", window_length=1000)
    consensus("sample_reads.fastq.gz", "sample_overlaps.paf.gz",
              "consensus_unit_scores", match=1, mismatch=-1, gap=-1)
    consensus("sample_reads.fastq.gz", "sample_overlaps.paf.gz",
              "consensus_e2e_scores", match=8, mismatch=-6, gap=-8)
    consensus("sample_reads.fastq.gz", "sample_overlaps.paf.gz",
              "consensus_banded", banded=True)
    fragment_kc("fragment_kc_ava")
    fragment("sample_reads.fastq.gz", "sample_ava_overlaps.paf.gz",
             "fragment_kf_paf_q")
    fragment("sample_reads.fasta.gz", "sample_ava_overlaps.paf.gz",
             "fragment_kf_paf_noq")
    fragment("sample_reads.fastq.gz", "sample_ava_overlaps.mhap.gz",
             "fragment_kf_mhap")


if __name__ == "__main__":
    main()
