"""Whole jobs through the single-device streams, against the mesh engines.

Every cell of the benchmark runs one chip: ``TpuAligner(mesh=None)`` and
``TpuPoaConsensus(mesh=None)``, hence ``_AlignStream`` (ragged chunks,
the band ladder) and ``_ConsensusStream`` (ragged groups). Under
conftest's eight virtual devices ``create_polisher`` builds the engines
with a mesh instead, which takes the bucketed aligner driver and the
padded consensus packer: the path most of tier-1 runs. Each case here
polishes one small assembly both ways and holds the two to the same
FASTA bytes, across the shapes that stress the layer filters (mixed
strands, reads without qualities, ``-f`` with several overlaps a read,
a mean-PHRED threshold that rejects rows, a fractional one) and both
``run()`` schedules (``num_threads`` 1: assemble then polish; 4: the
producer emits window ranges into the consensus session).

A first leg that quietly took the mesh path would make the comparison
empty, so it is held to what only the streams leave behind:
``align.packed_ahead`` is counted by ``_AlignStream`` alone, and the
consensus stream, which has no counter of its own, is counted as it is
opened.
"""

import pytest

from racon_tpu.core.polisher import PolisherType, create_polisher
from racon_tpu.obs import metrics
from racon_tpu.ops import poa

from test_columnar_init import polished_bytes, write_synthetic_assembly


def _fastq_to_fasta(fastq_path, fasta_path):
    """Strip the qualities: reads as a FASTA file gives them."""
    with open(fastq_path, "rb") as f:
        lines = f.read().split(b"\n")
    with open(fasta_path, "wb") as f:
        for i in range(0, len(lines) - 3, 4):
            f.write(b">" + lines[i][1:] + b"\n" + lines[i + 1] + b"\n")
    return fasta_path


def _single_device_engines():
    from racon_tpu.core.backends import NativeAligner, NativePoaConsensus
    from racon_tpu.ops.nw import TpuAligner

    return {"aligner": TpuAligner(fallback=NativeAligner(2), mesh=None),
            "consensus": poa.TpuPoaConsensus(
                3, -5, -4, fallback=NativePoaConsensus(3, -5, -4, 2),
                mesh=None)}


def _polish(reads, paf, layout, engines, *, type_, threads, quality):
    """One job; returns (polished bytes, the run's counters)."""
    metrics.clear_run()
    p = create_polisher(
        str(reads), str(paf), str(layout), type_=type_,
        quality_threshold=quality, num_threads=threads,
        aligner_backend="tpu", consensus_backend="tpu", **engines)
    out = polished_bytes(p.run(True))
    return out, metrics.snapshot()["counters"]


# id: (seed, contigs, reads as FASTA, polisher type, quality threshold)
_SHAPES = {
    "e2e-23-2": (23, 2, False, PolisherType.C, 10.0),
    "e2e-31-2": (31, 2, False, PolisherType.C, 10.0),
    "e2e-47-1": (47, 1, False, PolisherType.C, 10.0),
    # quality None: the mean-PHRED filter stands aside, min-span stays
    "dummy-quality": (29, 2, True, PolisherType.C, 10.0),
    # -f keeps every overlap of a read: several layers a read
    "f-multi-overlap": (37, 2, False, PolisherType.F, 10.0),
    # the inputs' b'9' = Q24 fails a 30.0 mean: every row is rejected
    "quality-30.0": (41, 1, False, PolisherType.C, 30.0),
    "quality-10.5": (43, 1, False, PolisherType.C, 10.5),
}

_CASES = [("e2e-23-2", 1), ("e2e-31-2", 4), ("e2e-47-1", 1),
          ("dummy-quality", 1), ("f-multi-overlap", 1),
          ("quality-30.0", 1), ("quality-10.5", 1),
          # the pipelined chunked emit at each filter shape
          ("dummy-quality", 4), ("f-multi-overlap", 4),
          ("quality-30.0", 4), ("quality-10.5", 4)]


@pytest.mark.parametrize("shape,threads", _CASES,
                         ids=[f"{s}-t{t}" for s, t in _CASES])
def test_streams_match_mesh_engines(tmp_path, monkeypatch, shape, threads):
    seed, n_contigs, as_fasta, type_, quality = _SHAPES[shape]
    reads, paf, layout = write_synthetic_assembly(tmp_path, seed=seed,
                                                  n_contigs=n_contigs)
    if as_fasta:
        reads = _fastq_to_fasta(reads, tmp_path / "reads.fasta")
    kw = dict(type_=type_, threads=threads, quality=quality)

    opened = []
    init = poa._ConsensusStream.__init__

    def counted(self, *args, **kwargs):
        opened.append(self)
        init(self, *args, **kwargs)

    monkeypatch.setattr(poa._ConsensusStream, "__init__", counted)
    got, counters = _polish(reads, paf, layout, _single_device_engines(),
                            **kw)
    assert "align.packed_ahead" in counters, sorted(counters)
    assert counters["align.chunks"] > 0
    assert opened, "no consensus stream was opened"
    # (at quality 30.0 every layer is rejected: the consensus has no
    # live window to dispatch, on either path)
    groups = counters.get("consensus.groups", 0)
    assert groups > 0 or quality == 30.0

    del opened[:]
    want, counters = _polish(reads, paf, layout, {}, **kw)
    assert "align.packed_ahead" not in counters and not opened
    assert counters["align.chunks"] > 0
    assert (counters.get("consensus.groups", 0) > 0) == (groups > 0)
    assert got == want
