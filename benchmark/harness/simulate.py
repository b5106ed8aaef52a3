"""The benchmark's input generator: one general, seeded simulator that a
traffic file parameterises.

A copy of ``tools/simulate.py`` (vectorized ONT-like reads, a mutated
draft, the exact PAF of every read against the draft) with its
hard-coded constants turned into the fields of a traffic file and the
genome given as an explicit list of contig sizes. Given one size, the
original's constants and ``read_len_draw: normal`` it reproduces
``tools/simulate.py`` byte for byte
(``benchmark/tests/test_simulate.py``). It imports numpy only: the
harness runs it in a throwaway child (``python simulate.py TRAFFIC.json
SEED OUT_DIR``), so the simulator's arrays never count towards the
measured process's peak RSS.

Traffic fields (all required, see ``benchmark/traffic/*.json``):
``contig_sizes`` (bases, in draft order), ``coverage``,
``read_len_mean``/``read_len_sd``/``read_len_min``/``read_len_max``,
``read_len_draw``, ``read_error`` and ``draft_error`` (``{"del", "ins",
"sub"}`` rates), ``quality_char`` (constant FASTQ quality) and
``overlaps`` (``"paf"`` is the only format this generator writes).

``read_len_draw`` is ``"normal"`` (the original's: lengths drawn from
the seed) or ``"quantiles"``: every seed gets the same set of lengths —
the clipped normal's evenly spaced quantiles — in another order. The
program cuts its aligner chunks by length, so with drawn lengths every
new seed brought new chunk shapes (2-12 new programs to compile, and a
different amount of padded work: PERF.md, PR 24); with the quantiles a
seed changes the genome, the placement and the errors, not the sizes.
"""

from __future__ import annotations

import json
import os
import statistics
import sys

import numpy as np

BASES = np.frombuffer(b"ACGT", dtype=np.uint8)

TRAFFIC_KEYS = ("contig_sizes", "coverage", "read_len_mean", "read_len_sd",
                "read_len_min", "read_len_max", "read_len_draw", "read_error",
                "draft_error", "quality_char", "overlaps")


def _mutate(seq, rng, del_p, ins_p, sub_p):
    """Apply indels via copy counts + substitutions; returns (mutated,
    copy_counts) where ``counts[i]`` is how many output bases truth base
    ``i`` produced (0 = deleted, 2 = insertion after)."""
    r = rng.random(len(seq))
    counts = np.ones(len(seq), np.int64)
    counts[r < del_p] = 0
    counts[(r >= del_p) & (r < del_p + ins_p)] = 2
    out = np.repeat(seq, counts)
    sub = rng.random(len(out)) < sub_p
    out[sub] = BASES[rng.integers(0, 4, int(sub.sum()))]
    return out, counts


_COMP = np.zeros(256, np.uint8)
_COMP[ord("A")] = ord("T")
_COMP[ord("T")] = ord("A")
_COMP[ord("C")] = ord("G")
_COMP[ord("G")] = ord("C")


def _revcomp(arr):
    return _COMP[arr[::-1]]


def check_traffic(traffic: dict) -> None:
    missing = [k for k in TRAFFIC_KEYS if k not in traffic]
    if missing:
        raise ValueError(f"traffic file lacks {missing}")
    if traffic["overlaps"] != "paf":
        raise ValueError(f"this generator writes PAF overlaps only, the "
                         f"traffic file asks for {traffic['overlaps']!r}")
    if traffic["read_len_draw"] not in ("normal", "quantiles"):
        raise ValueError(f"read_len_draw is 'normal' or 'quantiles', not "
                         f"{traffic['read_len_draw']!r}")
    if not traffic["contig_sizes"] or \
            min(traffic["contig_sizes"]) < traffic["read_len_min"]:
        raise ValueError("contig_sizes must be non-empty and no contig "
                         "shorter than read_len_min")


def simulate(traffic: dict, seed: int):
    """Returns (reads_fastq_bytes, paf_bytes, contigs_fasta_bytes,
    truths); ``truths`` is the list of truth contig byte strings."""
    check_traffic(traffic)
    rng = np.random.default_rng(seed)
    coverage = traffic["coverage"]
    mean_read, sd_read = traffic["read_len_mean"], traffic["read_len_sd"]
    min_read, max_read = traffic["read_len_min"], traffic["read_len_max"]
    rerr, derr = traffic["read_error"], traffic["draft_error"]
    qchar = traffic["quality_char"].encode()

    fastq_parts = []
    paf_lines = []
    fasta_parts = []
    truths = []
    read_id = 0
    for ci, size in enumerate(traffic["contig_sizes"]):
        truth = BASES[rng.integers(0, 4, size)]
        truths.append(truth.tobytes())
        tname = f"contig_{ci}".encode()

        draft, counts = _mutate(truth, rng, derr["del"], derr["ins"],
                                derr["sub"])
        # truth position -> draft position (exclusive prefix sum)
        t2d = np.concatenate(([0], np.cumsum(counts)))
        fasta_parts.append(b">" + tname + b"\n" + draft.tobytes() + b"\n")

        # reads: sample spans over truth, then inject independent errors
        n_reads = max(1, int(size * coverage) // mean_read)
        if traffic["read_len_draw"] == "quantiles":
            dist = statistics.NormalDist(mean_read, sd_read)
            raw = np.array([dist.inv_cdf((k + 0.5) / n_reads)
                            for k in range(n_reads)])[rng.permutation(n_reads)]
        else:
            raw = rng.normal(mean_read, sd_read, n_reads)
        lens = np.clip(raw.astype(np.int64), min_read, min(max_read, size))
        starts = rng.integers(0, np.maximum(1, size - lens))
        order = np.argsort(starts)  # deterministic, irrelevant to output
        lens, starts = lens[order], starts[order]
        seg_bounds = np.concatenate(([0], np.cumsum(lens)))
        cat = np.empty(seg_bounds[-1], np.uint8)
        for k in range(n_reads):
            cat[seg_bounds[k]:seg_bounds[k + 1]] = \
                truth[starts[k]:starts[k] + lens[k]]
        mut, mcounts = _mutate(cat, rng, rerr["del"], rerr["ins"],
                               rerr["sub"])
        out_lens = np.add.reduceat(mcounts, seg_bounds[:-1])
        out_bounds = np.concatenate(([0], np.cumsum(out_lens)))
        strands = rng.random(n_reads) < 0.5

        dlen = len(draft)
        for k in range(n_reads):
            rb = mut[out_bounds[k]:out_bounds[k + 1]]
            if strands[k]:
                rb = _revcomp(rb)
            name = f"read_{read_id}".encode()
            read_id += 1
            qual = qchar * len(rb)
            fastq_parts.append(b"@" + name + b"\n" + rb.tobytes()
                               + b"\n+\n" + qual + b"\n")
            tb = int(t2d[starts[k]])
            te = int(t2d[starts[k] + lens[k]])
            te = max(te, tb + 1)
            paf_lines.append(b"\t".join([
                name, str(len(rb)).encode(), b"0", str(len(rb)).encode(),
                b"-" if strands[k] else b"+",
                tname, str(dlen).encode(), str(tb).encode(),
                str(min(te, dlen)).encode(),
                str(min(len(rb), te - tb)).encode(),
                str(max(len(rb), te - tb)).encode(), b"255"]) + b"\n")

    return (b"".join(fastq_parts), b"".join(paf_lines),
            b"".join(fasta_parts), truths)


def input_paths(out_dir: str) -> dict:
    return {"reads": os.path.join(out_dir, "reads.fastq"),
            "overlaps": os.path.join(out_dir, "ovl.paf"),
            "draft": os.path.join(out_dir, "draft.fasta"),
            "truth": os.path.join(out_dir, "truth.fasta")}


def write_inputs(traffic: dict, seed: int, out_dir: str) -> dict:
    """Generate and write the input triple (+ truth contigs) to
    ``out_dir``; returns the paths."""
    reads, paf, contigs, truths = simulate(traffic, seed)
    os.makedirs(out_dir, exist_ok=True)
    paths = input_paths(out_dir)
    truth_fa = b"".join(b">contig_%d\n%s\n" % (i, t)
                        for i, t in enumerate(truths))
    for key, blob in (("reads", reads), ("overlaps", paf),
                      ("draft", contigs), ("truth", truth_fa)):
        with open(paths[key], "wb") as f:
            f.write(blob)
    return paths


if __name__ == "__main__":
    if len(sys.argv) != 4:
        sys.exit("usage: simulate.py TRAFFIC.json SEED OUT_DIR")
    with open(sys.argv[1], "r", encoding="utf-8") as fh:
        write_inputs(json.load(fh), int(sys.argv[2]), sys.argv[3])
