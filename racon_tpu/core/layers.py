"""Columnar layer storage: windows reference their read layers as
(offset, length) views into one concatenated read pool.

The round-7 columnar init left ONE per-layer Python loop standing: the
slice-and-append that copied every layer's bytes/quality into its
``Window`` (``layer_append_s`` in ``pipeline_init_breakdown``). This
module removes it. ``Polisher._assemble_layers`` builds a single
:class:`LayerStore` — a deduplicated byte pool of every referenced read
orientation plus flat per-layer ``(src, length, begin, end, win_id)``
arrays — and attaches each covered window an O(1) ``(store, row range)``
view. Window assembly becomes pure index arithmetic, and the consensus
packers write their device buffers **once, by one row copy per layer**
(:meth:`LayerStore.gather_qpw`: a native memcpy per pair row into the
group's own block) straight from the precomputed packed
``weight << 3 | code`` pool, instead of re-deriving codes and weights
from thousands of small bytes objects per pack.

The CPU engines (and any direct ``window.sequences`` consumer) see the
exact bytes they always did: :class:`~racon_tpu.core.window.Window`
materializes its layers lazily from the store on first access, so the
reference-semantics POA path and all recorded goldens are unchanged.
With ``evict_reads`` the original read payloads can be released as soon
as the store is built — the pool (raw bytes + qualities + packed lanes)
is the only copy the rest of the pipeline needs.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np

from .. import native

_CODE_LUT = np.full(256, 4, dtype=np.uint8)  # non-ACGT -> N code (4)
for _i, _b in enumerate(b"ACGT"):
    _CODE_LUT[_b] = _i


# one quality byte's ``weight << 3`` (phred-33 clipped at 0) and one base's
# code, as lanes: prepare's two table look-ups per pooled base
_WEIGHT_LANES = (np.maximum(np.arange(256) - 33, 0) << 3).astype(np.uint16)
_CODE_LANES = _CODE_LUT.astype(np.uint16)
_SLICE = 1 << 20  # pool bases per numpy call of prepare (a few ms each)


class PreparedPool:
    """What :meth:`LayerStore.prepare` makes of reads + overlaps alone:
    ``pool``/``qpool``/``qpw_pool`` as :class:`LayerStore` keeps them,
    per overlap its pool offset ``ov_off`` and whether it has qualities
    ``hq_ov``, and the wrapping prefix sums ``qsum`` over ``qpool``
    (``len(qpool) + 1`` entries) — the only field the store does not
    keep: its user drops it after the mean-PHRED filter."""

    __slots__ = ("pool", "qpool", "qpw_pool", "ov_off", "hq_ov", "qsum")

    def __init__(self, pool, qpool, qpw_pool, ov_off, hq_ov, qsum):
        self.pool = pool
        self.qpool = qpool
        self.qpw_pool = qpw_pool
        self.ov_off = ov_off
        self.hq_ov = hq_ov
        self.qsum = qsum


class LayerStore:
    """One run's layers, columnar. Per-layer arrays are window-major
    (sorted by ``win_id``, stable in overlap-stream order within a
    window — the POA tie-break contract); ``pool``/``qpool`` hold each
    referenced read orientation once, ``qpw_pool`` the device lane
    packing ``weight << 3 | code`` per pooled base (weights are
    phred-33 clipped at 0, or 1 for no-quality reads)."""

    __slots__ = ("pool", "qpool", "qpw_pool", "src", "length", "begin",
                 "end", "win_id", "has_qual", "row_bounds")

    def __init__(self, pool, qpool, qpw_pool, src, length, begin, end,
                 win_id, has_qual, row_bounds):
        self.pool = pool
        self.qpool = qpool
        self.qpw_pool = qpw_pool
        self.src = src
        self.length = length
        self.begin = begin
        self.end = end
        self.win_id = win_id
        self.has_qual = has_qual
        self.row_bounds = row_bounds

    @property
    def n_rows(self) -> int:
        return len(self.src)

    @classmethod
    def build(cls, prep: "PreparedPool", ov: np.ndarray, qb: np.ndarray,
              qe: np.ndarray, win_id: np.ndarray, begin: np.ndarray,
              end: np.ndarray, n_windows: int) -> "LayerStore":
        """The store over a prepared pool, from the per-layer columnar
        arrays of ``_assemble_layers`` (already window-major sorted):
        index arithmetic only. The pool holds every overlap's read, so a
        read whose rows were all filtered out is pooled and addressed by
        no row."""
        ov = np.asarray(ov, np.int64)
        qb = np.asarray(qb, np.int64)
        win_id = np.asarray(win_id, np.int64)
        return cls(prep.pool, prep.qpool, prep.qpw_pool,
                   prep.ov_off[ov] + qb, np.asarray(qe, np.int64) - qb,
                   np.asarray(begin, np.int64), np.asarray(end, np.int64),
                   win_id, prep.hq_ov[ov],
                   np.searchsorted(win_id, np.arange(n_windows + 1)))

    @staticmethod
    def prepare(data_refs: Sequence[bytes],
                qual_refs: Sequence[Optional[bytes]]) -> "PreparedPool":
        """The half of the store that needs no breaking point: the
        byte/quality/packed-lane pool over EVERY overlap and the quality
        prefix sums the mean-PHRED filter looks up.

        ``data_refs``/``qual_refs`` are per-overlap references into the
        read set (forward or reverse-complement orientation); the pool
        deduplicates them by object identity, so a read orientation
        referenced by many overlaps is pooled once. The polisher runs
        this beside the aligner's pack loop, so the passes over the pool
        are numpy calls on bounded slices that allocate nothing: each
        holds the interpreter lock for a few ms at most, and none makes
        the allocator map and unmap pool-sized temporaries under the
        pack loop's own."""
        n_ov = len(data_refs)
        off_of_obj = {}
        parts: List[bytes] = []
        qparts: List[bytes] = []
        part_hq: List[bool] = []
        pos = 0
        ov_off = np.zeros(n_ov, np.int64)
        for oi, d in enumerate(data_refs):
            key = id(d)
            off = off_of_obj.get(key)
            if off is None:
                off = pos
                off_of_obj[key] = off
                parts.append(d)
                q = qual_refs[oi]
                part_hq.append(q is not None)
                qparts.append(q if q is not None else b"\x00" * len(d))
                pos += len(d)
            ov_off[oi] = off
        pool = np.frombuffer(b"".join(parts), np.uint8)
        qpool = np.frombuffer(b"".join(qparts), np.uint8)
        hq_ov = np.fromiter((q is not None for q in qual_refs), bool, n_ov)

        # packed device lanes for the WHOLE pool, once (the per-group
        # packer then copies finished uint16 lanes, row by row), and a
        # span's quality sum as qsum[end] - qsum[begin]: unsigned sums
        # wrap, and the difference is exact while one span's true sum
        # fits the dtype (255 * the longest read bounds it). Both written
        # in place, slice by slice: no temporary the size of the pool
        part_len = np.fromiter(map(len, parts), np.int64, len(parts))
        part_off = np.cumsum(part_len) - part_len
        has_q = np.asarray(part_hq, bool)
        longest = int(part_len.max(initial=0))
        qsum = np.zeros(pos + 1,
                        np.uint32 if 255 * longest < 1 << 32 else np.uint64)
        qpw_pool = np.empty(pos, np.uint16)
        codes = np.empty(min(pos, _SLICE), np.uint16)
        for a in range(0, pos, _SLICE):
            b = min(a + _SLICE, pos)
            lanes = qpw_pool[a:b]
            np.take(_WEIGHT_LANES, qpool[a:b], mode="clip", out=lanes)
            np.take(_CODE_LANES, pool[a:b], mode="clip", out=codes[:b - a])
            lanes |= codes[:b - a]
            np.cumsum(qpool[a:b], dtype=qsum.dtype, out=qsum[a + 1:b + 1])
            qsum[a + 1:b + 1] += qsum[a]
        # a read without qualities weighs 1 per base (its qpool bytes are
        # 0, so no weight bit is set yet)
        for i in np.flatnonzero(~has_q):
            qpw_pool[part_off[i]:part_off[i] + part_len[i]] |= 1 << 3
        return PreparedPool(pool, qpool, qpw_pool, ov_off, hq_ov, qsum)

    # ------------------------------------------------------ device packing

    def gather_qpw(self, rows: np.ndarray, Lq: int,
                   out: Optional[np.ndarray] = None,
                   dest: Optional[np.ndarray] = None) -> np.ndarray:
        """The packed ``weight << 3 | code`` uint16 lane block
        [len(rows), Lq] for the given layer rows — exactly the array
        ``TpuPoaConsensus._pack_shard`` ships to the device (a row
        shorter than ``Lq`` zero-padded, a longer one cut at ``Lq``).

        With ``out`` (a zeroed C-contiguous [B, Lq] uint16 block) and
        ``dest``, layer ``rows[i]`` is written into ``out[dest[i]]`` and
        ``out`` is returned: the packer's block is written once, in
        place. Either way the lanes land by row copies — the native
        copier's memcpys, or per-row slice assignments where the native
        core is not available — never through a [rows, Lq] index
        matrix."""
        src, lens = self.src[rows], self.length[rows]
        if out is None:
            out = np.zeros((len(src), Lq), np.uint16)
            dest = np.arange(len(src))
        if native.available():
            native.copy_lane_rows(self.qpw_pool, src, lens, dest, out)
        else:
            pool = self.qpw_pool
            for d, s, n in zip(np.asarray(dest).tolist(), src.tolist(),
                               np.minimum(lens, Lq).tolist()):
                out[d, :n] = pool[s:s + n]
        return out

    # ---------------------------------------------------- materialization

    def materialize_into(self, win, r0: int, r1: int) -> None:
        """Append rows [r0, r1) to ``win``'s layer lists as real bytes —
        the lazy CPU-path escape hatch (fallback engines, direct
        ``window.sequences`` consumers). Byte-exact: the pool stores the
        raw read bytes, so non-ACGT characters survive untouched."""
        for r in range(r0, r1):
            s = int(self.src[r])
            ln = int(self.length[r])
            win._seqs.append(self.pool[s:s + ln].tobytes())
            win._quals.append(self.qpool[s:s + ln].tobytes()
                              if self.has_qual[r] else None)
            win._pos.append((int(self.begin[r]), int(self.end[r])))
