"""Jit'd minimizer seeding — stage one of the first-party overlapper.

The reference pipeline demands precomputed overlaps from an external
mapper (minimap2), so PAF/MHAP/SAM parsing is its entire ingest story.
``--overlaps auto`` replaces that with an in-process minimizer-seed →
chain overlapper (ROADMAP item 5); this module is the seeding half:

- sequences pack host-side into 2-bit code arrays (A/C/G/T → 0..3,
  anything else → 4, which invalidates every k-mer covering it), every
  one cut into slices that fit a row of :data:`SEED_ROW` bases, and the
  rows fill ONE fixed ``[SEED_BATCH, SEED_ROW]`` arena: one geometry,
  hence one compiled program, for every input (reads of any length,
  contigs, the tail batch — PR 34: the pow2 length and batch classes
  this replaced compiled a program per class and new ones per input);
- one jit'd pass per batch builds forward and reverse-complement k-mer
  codes (k static shifted slices), takes the strand-canonical minimum
  (``fwd == rc`` palindrome ties are skipped, like minimap2), scrambles
  it through an invertible 32-bit finalizer so rank ties don't follow
  base composition, and selects each w-window's leftmost minimum with a
  strict-< iterative sweep (deterministic: no argmin tie ambiguity);
- the windows' picks mark a per-position mask; the host (or,
  under ``RACON_TPU_RESIDENT=1``, a device compaction kernel that ships
  only the selected entries over the link) flattens the batch into one
  flat ``(hash, seq_id, pos, strand)`` table for the matcher
  (:mod:`racon_tpu.ops.chain`).

Sequences longer than a row (contig targets, long reads) are sliced
into bounded window-start spans so the arena never scales with sequence
length; slices overlap by ``k + w - 2`` bases and each window is owned
by exactly one slice, so the union equals the whole-sequence scan (the
plain reference :func:`racon_tpu.models.overlap.minimizers_np` asserts
this in tests/test_overlapper.py).
"""

from __future__ import annotations

import functools
import hashlib
import threading
from collections import OrderedDict
from typing import Iterator, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import obs
from ..models.overlap import BASE_LUT as _BASE_LUT
from ..models.overlap import HASH_MAX as _HASH_MAX
from ..obs import device_time, metrics
from ..parallel import fetch_global

# defaults mirrored by the RACON_TPU_OVERLAP_K/W flags (k=15/w=5: ONT
# read-vs-draft seeding; ~1/3 of positions carry a minimizer)
DEFAULT_K = 15
DEFAULT_W = 5
# minimizer-arena budget in cells: every per-position working array
# (codes, fwd/rc kmers, hashes, mask) is B*L
SEED_ARENA_CELLS = 1 << 22
# the arena's ONE geometry. A row holds one slice of one sequence:
# SEED_ROW - (k + w - 2) window starts plus the k + w - 2 bases the last
# of them reads. 8 kb keeps a typical long read to one or two rows (a
# tail row is the only padding a sequence costs) and a launch's full
# fetch (6 bytes a cell) at 24 MiB
SEED_ROW = 1 << 13
SEED_BATCH = SEED_ARENA_CELLS // SEED_ROW


# -------------------------------------------------------------- geometry

def _slice_starts(k: int, w: int) -> int:
    """Window starts one row owns: the row less the ``k + w - 2`` bases
    the last window reads past its start."""
    return SEED_ROW - (k + w - 2)


# --------------------------------------------------------------- kernels

def _mix32(h):
    """Invertible 32-bit integer finalizer (murmur3 fmix32): minimizer
    rank stops following base composition, and distinct canonical codes
    can never collide (bijective on the uint32 domain)."""
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> jnp.uint32(13))
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> jnp.uint32(16))
    return h


@functools.partial(jax.jit, static_argnames=("k", "w", "L"))
def _minimizer_kernel(codes, lens, nwin, *, k: int, w: int, L: int):
    """One minimizer pass over a ``[B, L]`` code batch.

    ``lens`` bounds each row's real bases, ``nwin`` its owned window
    starts (slice discipline: overlap-region windows belong to the next
    slice). Returns ``(hash [B, P] uint32, strand [B, P] bool,
    selected [B, P] bool, selected per row [B] int32)`` with ``P = L -
    k + 1``; the last is the small output the occupancy ledger
    watches."""
    P = L - k + 1
    B = codes.shape[0]
    base = codes.astype(jnp.uint32)
    f = jnp.zeros((B, P), jnp.uint32)
    r = jnp.zeros((B, P), jnp.uint32)
    bad = jnp.zeros((B, P), jnp.bool_)
    for j in range(k):
        c = base[:, j:j + P]
        bad = bad | (c > jnp.uint32(3))
        cc = c & jnp.uint32(3)
        f = (f << jnp.uint32(2)) | cc
        r = (r >> jnp.uint32(2)) | ((jnp.uint32(3) - cc)
                                    << jnp.uint32(2 * (k - 1)))
    pos = jnp.arange(P, dtype=jnp.int32)
    in_seq = pos[None, :] + k <= lens[:, None]
    strand = r < f  # canonical k-mer is the reverse complement
    h = _mix32(jnp.minimum(f, r))
    h = jnp.where(bad | (f == r) | ~in_seq, jnp.uint32(_HASH_MAX), h)

    # leftmost strict-< windowed minimum over w consecutive k-mer slots
    W = P - w + 1
    minv = h[:, 0:W]
    pick = jnp.zeros((B, W), jnp.int32)
    for j in range(1, w):
        cand = h[:, j:j + W]
        take = cand < minv
        minv = jnp.where(take, cand, minv)
        pick = jnp.where(take, jnp.int32(j), pick)
    wvalid = (pos[None, :W] < nwin[:, None]) \
        & (pos[None, :W] + (w + k - 1) <= lens[:, None]) \
        & (minv != jnp.uint32(_HASH_MAX))
    # slot p is selected when a valid window j slots to its left picked
    # offset j: w shifted compares, no scatter (with a scatter of the
    # arena's 4 M picks this program took the chip's compiler 15.7 s,
    # on the feeding thread's path; PR 34)
    pick = jnp.where(wvalid, pick, jnp.int32(-1))
    sel = jnp.zeros((B, P), jnp.bool_)
    for j in range(w):
        sel = sel | jnp.pad(pick == j, ((0, 0), (j, w - 1 - j)))
    return h, strand, sel, jnp.sum(sel.astype(jnp.int32), axis=1)


@functools.lru_cache(maxsize=None)
def _seed_geometry(B: int, L: int, k: int, w: int) -> str:
    """The occupancy ledger's join key of a ``[B, L]`` minimizer batch
    (both of its programs), from the stream and the warm-up alike."""
    return device_time.geometry(B=B, w=w, L=L, k=k)


@jax.jit
def _compact_kernel(h, strand, sel):
    """Device-side table compaction (the resident path): selected
    entries pack to the front in row-major order — identical to the
    host ``np.nonzero`` walk — so only ``n_selected`` elements ever
    cross the host link instead of the full ``[B, P]`` arenas."""
    B, P = h.shape
    flat = sel.reshape(-1)
    rank = jnp.cumsum(flat.astype(jnp.int32))
    total = rank[-1]
    idx = jnp.where(flat, rank - 1, jnp.int32(B * P))
    lin = jnp.arange(B * P, dtype=jnp.int32)
    out_h = jnp.zeros((B * P + 1,), jnp.uint32).at[idx].set(h.reshape(-1))
    out_row = jnp.zeros((B * P + 1,), jnp.int32).at[idx].set(lin // P)
    out_pos = jnp.zeros((B * P + 1,), jnp.int32).at[idx].set(lin % P)
    out_s = jnp.zeros((B * P + 1,), jnp.bool_).at[idx].set(
        strand.reshape(-1))
    return out_h, out_row, out_pos, out_s, total


# ------------------------------------------------------------ host driver

def _iter_chunks(seqs: List[bytes], k: int, w: int
                 ) -> Iterator[Tuple[int, int, bytes, int]]:
    """``(seq_id, window_start_offset, byte_slice, n_windows)`` chunks
    in ``(seq_id, offset)`` order: whole short sequences, bounded
    overlapping slices of those longer than a row."""
    span = _slice_starts(k, w)
    for sid, s in enumerate(seqs):
        L = len(s)
        if L < k + w - 1:
            continue  # no complete window fits
        n_total = L - (k + w - 1) + 1
        for s0 in range(0, n_total, span):
            n_here = min(span, n_total - s0)
            end = min(L, s0 + n_here + (k + w - 2))
            yield sid, s0, s[s0:end], n_here


# target seed-table cache: the target set is
# identical across every shard of one run and across serve jobs naming
# the same draft, so the table is keyed by a content fingerprint +
# (k, w) and rebuilt only when the inputs actually change. Entries are
# treated as immutable by every consumer (the matcher copies via fancy
# indexing / padding), so sharing the arrays is safe.
_TABLE_CACHE: "OrderedDict[Tuple[bytes, int, int], tuple]" = OrderedDict()
_TABLE_CACHE_CAP = 4
_TABLE_CACHE_LOCK = threading.Lock()


def _fingerprint(seqs: List[bytes], k: int, w: int
                 ) -> Tuple[bytes, int, int]:
    """Content fingerprint of a sequence set: blake2b over the count,
    each length, and each byte string — any byte change changes the
    key, and (k, w) ride alongside so parameter sweeps never alias."""
    hsh = hashlib.blake2b(digest_size=16)
    hsh.update(len(seqs).to_bytes(8, "little"))
    for s in seqs:
        hsh.update(len(s).to_bytes(8, "little"))
        hsh.update(s)
    return hsh.digest(), k, w


def clear_table_cache() -> None:
    """Drop every cached target table (tests / memory pressure)."""
    with _TABLE_CACHE_LOCK:
        _TABLE_CACHE.clear()


def build_seed_table(seqs: List[bytes], *, k: int = DEFAULT_K,
                     w: int = DEFAULT_W, resident: bool = False,
                     cache: bool = False
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                np.ndarray]:
    """The flat minimizer table of a sequence set: parallel numpy arrays
    ``(hash uint32, seq_id int32, pos int32, strand bool)`` in
    deterministic (bucket-grouped, sequence-order) row order.

    ``resident=True`` compacts on device and fetches only the selected
    entries (counted into the ``dataflow.*`` bytes ledger); the host
    path fetches the full masks and compacts with numpy. Both produce
    identical tables (tests assert the parity).

    ``cache=True`` (the target side of the overlapper) consults the
    fingerprint-keyed table
    cache first: a hit skips packing, kernels, and fetches entirely —
    counted in ``overlap.cache_hits`` and credited to
    ``dataflow.bytes_avoided`` at the table's own wire size."""
    ckey = None
    if cache:
        ckey = _fingerprint(seqs, k, w)
        with _TABLE_CACHE_LOCK:
            hit = _TABLE_CACHE.get(ckey)
            if hit is not None:
                _TABLE_CACHE.move_to_end(ckey)
        if hit is not None:
            metrics.inc("overlap.cache_hits")
            metrics.inc("overlap.minimizers", int(hit[0].size))
            # the fetch (resident wire size) + kernels this hit skipped
            metrics.inc("dataflow.bytes_avoided", int(hit[0].size) * 10)
            return hit
        metrics.inc("overlap.cache_misses")
    chunks = list(_iter_chunks(seqs, k, w))
    B, L = SEED_BATCH, SEED_ROW

    hs: List[np.ndarray] = []
    ids: List[np.ndarray] = []
    ps: List[np.ndarray] = []
    ss: List[np.ndarray] = []
    for begin in range(0, len(chunks), B):
        part = chunks[begin:begin + B]
        codes = np.full((B, L), 4, np.uint8)
        lens = np.zeros(B, np.int32)
        nwin = np.zeros(B, np.int32)
        for i, (_, _, blob, n_here) in enumerate(part):
            arr = _BASE_LUT[np.frombuffer(blob, np.uint8)]
            codes[i, :arr.size] = arr
            lens[i] = arr.size
            nwin[i] = n_here
        with obs.span("overlap.seed.dispatch", rows=len(part)):
            codes_d = jnp.asarray(codes)
            device_time.submit("h2d", "overlap.seed.put", codes_d)
            # graftlint: disable=jit-shape-hazard (k/w are run-constant flag values — one compile per run; L is the one row length)
            h, strand, sel, nsel = _minimizer_kernel(codes_d, lens, nwin,
                                                     k=k, w=w, L=L)
            geom = _seed_geometry(B, L, k, w)
            device_time.submit("exec", "_minimizer_kernel", nsel, geom)
            if resident:
                h, row, pcol, strand, total = _compact_kernel(
                    h, strand, sel)
                device_time.submit("exec", "_compact_kernel", total, geom)
        if resident:
            with obs.span("overlap.seed.fetch", rows=len(part)):
                n_host = fetch_global([total])[0]
                n = int(n_host)
                h_np, rows, cols, s_np = fetch_global(
                    [h[:n], row[:n], pcol[:n], strand[:n]])
            fetched = n * 10  # 4 + 4 + 1 + 1 bytes per entry
            metrics.inc("dataflow.bytes_fetched", fetched)
            metrics.inc("dataflow.bytes_avoided",
                        max(0, B * (L - k + 1) * 6 - fetched))
        else:
            with obs.span("overlap.seed.fetch", rows=len(part)):
                h_full, sel_np, s_full = fetch_global(
                    [h, sel, strand])
            rows, cols = np.nonzero(sel_np)
            h_np = h_full[rows, cols]
            s_np = s_full[rows, cols]
        keep = h_np != np.uint32(_HASH_MAX)
        rows, cols = rows[keep], cols[keep]
        chunk_ids = np.fromiter((c[0] for c in part), np.int32,
                                len(part))
        chunk_off = np.fromiter((c[1] for c in part), np.int32,
                                len(part))
        hs.append(h_np[keep])
        ids.append(chunk_ids[rows])
        ps.append(chunk_off[rows] + cols.astype(np.int32))
        ss.append(np.asarray(s_np)[keep])
        metrics.inc("overlap.seed_lanes_total", B * L)
        metrics.inc("overlap.seed_lanes_occupied", int(lens.sum()))
    if not hs:
        z = np.zeros(0, np.int32)
        table = (np.zeros(0, np.uint32), z, z, np.zeros(0, bool))
        if ckey is not None:
            _table_cache_put(ckey, table)
        return table
    h_all = np.concatenate(hs)
    id_all = np.concatenate(ids)
    p_all = np.concatenate(ps)
    s_all = np.concatenate(ss)
    # canonical (seq_id, pos) order. Rows come in (seq_id, offset)
    # order and a window's minimizer never lies left of the previous
    # window's, so the walk above is that order already; the one
    # repeat — a position picked by windows on both sides of a slice
    # boundary emits once per slice — sits beside its twin
    key = (id_all.astype(np.int64) << 32) | p_all
    if key.size > 1 and not bool(np.all(key[1:] >= key[:-1])):
        order = np.argsort(key, kind="stable")
        h_all, id_all, p_all, s_all = (h_all[order], id_all[order],
                                       p_all[order], s_all[order])
        key = key[order]
    uniq = np.ones(h_all.size, bool)
    uniq[1:] = key[1:] != key[:-1]
    if not uniq.all():
        h_all, id_all, p_all, s_all = (h_all[uniq], id_all[uniq],
                                       p_all[uniq], s_all[uniq])
    table = (h_all, id_all, p_all, s_all)
    metrics.inc("overlap.minimizers", int(table[0].size))
    if ckey is not None:
        _table_cache_put(ckey, table)
    return table


def _table_cache_put(ckey, table) -> None:
    with _TABLE_CACHE_LOCK:
        _TABLE_CACHE[ckey] = table
        _TABLE_CACHE.move_to_end(ckey)
        while len(_TABLE_CACHE) > _TABLE_CACHE_CAP:
            _TABLE_CACHE.popitem(last=False)


# -------------------------------------------------------------- warm-up

_warmed_shapes: set = set()


def _warmup_shapes(est_len: int, est_seqs: int) -> List[Tuple[int, int]]:
    """The ``(L, B)`` batch geometries a run dispatches: the one arena,
    whatever the estimates (zero estimates: nothing to warm)."""
    if est_len <= 0 or est_seqs <= 0:
        return []
    return [(SEED_ROW, SEED_BATCH)]


def warmup_async(est_len: int, est_seqs: int,
                 k: int = DEFAULT_K, w: int = DEFAULT_W):
    """Background warm-up compilation of the expected minimizer batch
    shapes (the overlapper analog of ``TpuAligner.warmup_async``):
    executes the kernel once per shape on near-empty inputs while the
    host packs real code arrays. Shape-deduped; returns the thread
    (for tests) or None when skipped (zero estimates, every shape
    already warmed)."""
    shapes = [(L, B, k, w) for L, B in _warmup_shapes(est_len, est_seqs)
              if (L, B, k, w) not in _warmed_shapes]
    if not shapes:
        return None
    _warmed_shapes.update(shapes)

    def _one(L, B, kk, ww):
        codes = np.full((B, L), 4, np.uint8)
        ones = np.ones(B, np.int32)
        # graftlint: disable=jit-shape-hazard (k/w are run-constant flag values — one compile per run; L is the pow2 bucket)
        out = _minimizer_kernel(codes, ones, ones, k=kk, w=ww, L=L)
        # the dummy occupies the device like any program: kind "warm"
        device_time.submit("warm", "_minimizer_kernel", out[3],
                           _seed_geometry(B, L, kk, ww))
        jax.block_until_ready(out[3])

    def _run():
        for L, B, kk, ww in shapes:
            try:
                _one(L, B, kk, ww)
            except Exception as e:
                from ..utils.logger import log_swallowed
                log_swallowed(
                    f"minimizer warm-up shape {(L, B)} failed (the "
                    f"run's own shapes still compile on first use)", e)

    import threading

    # graftlint: disable=thread-lifecycle (droppable best-effort warm-up; daemon dies harmlessly at exit)
    th = threading.Thread(target=_run, daemon=True,
                          name="racon-seed-warmup")
    th.start()
    return th
