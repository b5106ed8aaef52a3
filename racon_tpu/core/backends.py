"""Pluggable compute backends for the polishing pipeline.

The reference dispatches CPU (edlib/spoa) vs GPU (cudaaligner/cudapoa) inside
``createPolisher`` (``src/polisher.cpp:135-158``) and routes accelerator
rejects back to the CPU path (``src/cuda/cudapolisher.cpp:195-199,344-367``).
Here the same seams are explicit backend objects:

- ``AlignerBackend.align_batch(pairs) -> cigars`` fills the role of
  CUDABatchAligner (``src/cuda/cudaaligner.cpp``);
- ``ConsensusBackend.run(windows, trim) -> polished flags`` fills the role of
  CUDABatchProcessor (``src/cuda/cudabatch.cpp``).

TPU implementations live in ``racon_tpu.ops`` and are selected with
``backend="tpu"``; every TPU backend keeps the CPU implementation as its
reject-fallback, mirroring the reference's contract.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

from ..models.nw import nw_align
from ..models.poa import PoaAlignmentEngine
from .. import native


class PythonAligner:
    """Pure-Python banded NW (fallback of last resort)."""

    def align_batch(self, pairs: Sequence[Tuple[bytes, bytes]]) -> List[str]:
        return [nw_align(q, t) for q, t in pairs]


class NativeAligner:
    """C++ banded NW with an internal dynamic work queue over threads
    (host analog of the reference's batch fill/process loop,
    ``src/cuda/cudapolisher.cpp:98-160``)."""

    def __init__(self, num_threads: int = 1):
        self.num_threads = num_threads
        if not native.available():
            raise RuntimeError("native library unavailable")

    def align_batch(self, pairs: Sequence[Tuple[bytes, bytes]]) -> List[str]:
        return native.nw_cigar_batch(list(pairs), num_threads=self.num_threads)


class PythonPoaConsensus:
    """Spoa-semantics POA over windows in pure Python (sequential; the
    oracle the native engine is validated against)."""

    # pipelined-polish chunk sizing (Polisher.run): the host engines have
    # no fixed device-group geometry, so prefer large streamed ranges —
    # fewer run() calls keep the native thread pool saturated and bound
    # the GIL traffic between the layer producer and the packer
    group_pairs_hint = 1 << 18
    # optional streaming-session seam (round 10): device engines expose
    # stream(trim, band_hint) -> session for double-buffered async
    # dispatch; host engines have no device pipeline to overlap, so the
    # Polisher falls back to per-range blocking run() calls
    stream = None

    def __init__(self, match: int, mismatch: int, gap: int,
                 num_threads: int = 1):
        self.engine = PoaAlignmentEngine(match, mismatch, gap)
        self.num_threads = num_threads

    def run(self, windows, trim: bool, progress=None) -> List[bool]:
        flags: List[bool] = []
        for k, w in enumerate(windows):
            flags.append(w.generate_consensus(self.engine, trim))
            if progress is not None:
                progress(k + 1, len(windows))
        return flags


class NativePoaConsensus:
    """C++ POA engine threaded over windows (reference CPU path,
    ``src/polisher.cpp:490-503`` with per-thread spoa engines). Produces
    byte-identical consensuses to :class:`PythonPoaConsensus`; windows the
    native engine flags as failed are re-polished by the Python engine."""

    group_pairs_hint = 1 << 18  # see PythonPoaConsensus
    stream = None               # see PythonPoaConsensus

    def __init__(self, match: int, mismatch: int, gap: int,
                 num_threads: int = 1):
        if not native.available():
            raise RuntimeError("native library unavailable")
        self.match, self.mismatch, self.gap = match, mismatch, gap
        self.num_threads = num_threads
        self.engine = PoaAlignmentEngine(match, mismatch, gap)

    def run(self, windows, trim: bool, progress=None) -> List[bool]:
        flags: List[bool] = []
        n = len(windows)
        # with a progress callback, slice the batch so the reference's
        # 20-bin bar is observable mid-run — but never below 4 windows per
        # pool thread, or the slices starve the native thread pool
        chunk = (max(1, -(-n // 20), 4 * self.num_threads)
                 if progress is not None else max(1, n))
        for start in range(0, n, chunk):
            part = windows[start:start + chunk]
            results = native.poa_consensus_batch(
                part, trim, self.match, self.mismatch, self.gap,
                self.num_threads)
            for w, (consensus, polished, failed) in zip(part, results):
                if failed:
                    flags.append(w.generate_consensus(self.engine, trim))
                else:
                    w.consensus = consensus
                    flags.append(polished)
            if progress is not None:
                progress(min(start + chunk, n), n)
        return flags


# Historical alias: the CPU consensus used by tests/benchmarks; prefers the
# threaded native engine and falls back to pure Python.
def CpuPoaConsensus(match: int, mismatch: int, gap: int,
                    num_threads: int = 1):
    if native.available():
        return NativePoaConsensus(match, mismatch, gap, num_threads)
    return PythonPoaConsensus(match, mismatch, gap, num_threads)


def _auto_mesh(mesh):
    """Resolve the device mesh for an accelerated backend: an explicit
    mesh wins; otherwise every visible device is engaged when there is
    more than one — the reference's `-c N` uses every visible GPU
    (``src/cuda/cudapolisher.cpp:46,72-83``), and the TPU analog is a 1-D
    ``shard_map`` mesh over ``jax.devices()``."""
    if mesh is not None:
        return mesh
    import jax

    from ..parallel import get_mesh
    if len(jax.devices()) > 1:
        return get_mesh()
    return None


def _require_native(what: str) -> None:
    """The device engines hand their rejects (band escapes, over-long
    pairs, overflowed windows) to the native host engines — racon's
    accelerator->CPU contract. Without the native core those would be
    the pure-Python engines (seconds per overlap), a silent
    orders-of-magnitude downgrade, so ``backend="tpu"`` requires it."""
    if not native.available():
        raise ValueError(
            f"TPU {what} backend needs the native host core for its "
            f"reject path, and it is unavailable (g++ missing or the "
            f"build failed — see the 'native:' warning above; "
            f"`python -c 'from racon_tpu import native; "
            f"native.build(force=True)'` shows the compiler output)")


def make_aligner(backend: str, num_threads: int, num_batches: int = 1,
                 mesh=None, device=None):
    if backend == "python":
        return PythonAligner()
    if backend in ("native", "cpu"):
        return NativeAligner(num_threads)
    if backend == "tpu":
        try:
            from ..ops.nw import TpuAligner
        except ImportError as e:
            raise ValueError(f"TPU aligner backend unavailable: {e}")
        _require_native("aligner")
        # an explicit chip pin is single-device by definition: the chip
        # scheduler builds one engine per local device, so the
        # every-visible-device auto-mesh must NOT engage under it
        return TpuAligner(fallback=NativeAligner(num_threads),
                          num_batches=num_batches,
                          mesh=None if device is not None
                          else _auto_mesh(mesh),
                          device=device)
    if backend == "auto":
        if native.available():
            return NativeAligner(num_threads)
        return PythonAligner()
    raise ValueError(f"unknown aligner backend {backend!r}")


def make_consensus(backend: str, match: int, mismatch: int, gap: int,
                   num_threads: int = 1, num_batches: int = 1,
                   banded: bool = False, mesh=None, device=None):
    if backend == "python":
        return PythonPoaConsensus(match, mismatch, gap, num_threads)
    if backend in ("native", "cpu"):
        return NativePoaConsensus(match, mismatch, gap, num_threads)
    if backend == "auto":
        return CpuPoaConsensus(match, mismatch, gap, num_threads)
    if backend == "tpu":
        try:
            from ..ops.poa import BAND, TpuPoaConsensus
        except ImportError as e:
            raise ValueError(f"TPU consensus backend unavailable: {e}")
        # -b halves the alignment band (the reference's banded-cudapoa
        # speed/accuracy trade, src/main.cpp:124-126); a chip pin
        # (device) suppresses the auto-mesh — see make_aligner
        _require_native("consensus")
        return TpuPoaConsensus(match, mismatch, gap,
                               fallback=NativePoaConsensus(
                                   match, mismatch, gap, num_threads),
                               band=BAND // 2 if banded else BAND,
                               num_batches=num_batches,
                               mesh=None if device is not None
                               else _auto_mesh(mesh),
                               device=device)
    raise ValueError(f"unknown consensus backend {backend!r}")
