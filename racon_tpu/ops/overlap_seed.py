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
- the windows' picks mark a per-position mask; the host flattens the
  batch into one flat ``(hash, seq_id, pos, strand)`` table for the
  matcher (:mod:`racon_tpu.ops.chain`);
- the arenas of one build are a stream (:class:`_SeedStream`, PR 45):
  arena k + 1 is packed and launched while arena k's planes cross back
  and a worker writes its selected entries once, at their place in the
  final arrays (the kernel's per-row counts size the slice).

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
from collections import OrderedDict, deque
from concurrent.futures import ThreadPoolExecutor
from typing import Iterator, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import obs
from ..models.overlap import BASE_LUT as _BASE_LUT
from ..models.overlap import HASH_MAX as _HASH_MAX
from ..obs import device_time, metrics
from ..parallel import fetch_global, is_multihost
from .nw import _copy_rows

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


# arenas whose planes are on their way into the table at once (one worker
# each): arena k + 1 is packed and launched while arena k's planes cross
# back and are compacted behind it, and a launch waits only for the
# arena two in front of it. Fixed here: nothing a caller knows changes it
SEED_IN_FLIGHT = 2
_CODE_TABLE = _BASE_LUT.tobytes()


def _pack_arena(part, B: int, L: int):
    """One arena's host arrays from its chunks: ``codes [B, L]`` (4, no
    base, wherever no sequence lies), ``lens`` / ``nwin [B]``, and the
    rows' ``seq_id`` / window-start offset. One remap over the chunks'
    bytes back to back, then a row copy (``ops/nw.py`` ``_copy_rows``:
    native where the core is built)."""
    n = len(part)
    blobs = [c[2] for c in part]
    lens = np.zeros(B, np.int32)
    nwin = np.zeros(B, np.int32)
    lens[:n] = np.fromiter(map(len, blobs), np.int32, n)
    nwin[:n] = np.fromiter((c[3] for c in part), np.int32, n)
    codes = np.full((B, L), 4, np.uint8)
    _copy_rows(b"".join(blobs).translate(_CODE_TABLE), lens[:n], codes)
    ids = np.fromiter((c[0] for c in part), np.int32, n)
    offs = np.fromiter((c[1] for c in part), np.int32, n)
    return codes, lens, nwin, ids, offs


def _write_entries(out, offset: int, h, ids, pos, strand) -> int:
    """Write one arena's entries (row-major order) into the table's four
    arrays from ``offset`` on, less every entry whose ``(seq_id, pos)``
    repeats its left neighbour's: a position picked by windows on both
    sides of a slice seam emits once per slice and sits beside its
    twin. Returns the entries written."""
    if h.size > 1:
        twin = (ids[1:] == ids[:-1]) & (pos[1:] == pos[:-1])
        if twin.any():
            keep = np.concatenate(([True], ~twin))
            h, ids, pos, strand = h[keep], ids[keep], pos[keep], strand[keep]
    n = int(h.size)
    for dst, src in zip(out, (h, ids, pos, strand)):
        dst[offset:offset + n] = src
    return n


def _compact_arena(out, offset: int, reserved: int, planes, ids, offs,
                   row_sel) -> int:
    """An arena's fetched ``(hash, strand, selected)`` planes into its
    slice ``[offset, offset + reserved)`` of the table ``out``: every
    selected slot of the rows that hold a chunk, in row-major order,
    written once (``native/lanes.cpp`` ``rt_compact_seed_rows``; the
    ``np.nonzero`` walk where the native core is absent). Returns the
    entries written: ``reserved`` less the arena's seam repeats."""
    from .. import native
    h, strand, sel = map(np.ascontiguousarray, planes)
    if native.available():
        return native.compact_seed_rows(h, sel, strand, ids, offs, row_sel,
                                        out, offset, reserved)
    rows, cols = np.nonzero(sel[:len(ids)])
    return _write_entries(out, offset, h[rows, cols], ids[rows],
                          offs[rows] + cols.astype(np.int32),
                          strand[rows, cols])


class _Arena:
    """One launched arena on its way into the table."""

    __slots__ = ("ids", "offs", "device", "row_sel", "offset", "reserved",
                 "count", "fetched")

    def __init__(self, ids, offs, device):
        self.ids = ids
        self.offs = offs
        self.device = device        # the kernel's outputs, until fetched
        self.row_sel = None         # selected slots a row
        self.offset = 0             # its slice of the table: from here,
        self.reserved = 0           # this many entries at most,
        self.count = 0              # this many written
        self.fetched = False


class _SeedStream:
    """The arena loop of :func:`build_seed_table` as a stream.

    The calling thread packs arena k + 1 (host only) and launches it —
    every submission from this thread, in arena order, so the occupancy
    ledger charges the idle to its ``overlap.seed*`` spans — while a
    worker fetches arena k's planes and compacts them. The kernel's
    per-row selected counts size an arena's slice of the final
    ``(hash, seq_id, pos, strand)`` arrays when the arena behind it has
    been launched, so slices lie in launch order whatever order the
    workers finish in, and every entry is written once, in place.
    A seam repeat is dropped where it is written (inside an arena) or
    when the slices are closed up (its twin in the arena before); the
    holes this leaves — none for reads under a row's length — are
    closed by :meth:`finish`.

    One arena (a draft of a few Mbp, the tests' inputs) and a
    multi-host run start no thread: pack, launch, fetch, compact on the
    calling thread, the order of events a build has always had."""

    def __init__(self, n_arenas: int, windows: int, k: int, w: int):
        self.k, self.w = k, w
        self.B, self.L = SEED_BATCH, SEED_ROW
        # a window picks one slot, so a table holds `windows` entries at
        # most; the minimizers of a random sequence take 2 / (w + 1) of
        # them. A quarter over that to begin with: low-complexity input
        # grows the arrays (a copy), nothing else
        self.limit = windows
        self.out = self._alloc(min(windows,
                                   int(windows * 2.5 / (w + 1)) + 1024))
        self.used = 0
        self.arenas: List[_Arena] = []            # in launch order
        self.front: Optional[_Arena] = None       # launched, slice not sized
        self.writing: deque = deque()             # (arena, future) at a worker
        self.ordered = True
        self._last_key = -1
        self.pool = None
        # across hosts a fetch is a collective, and collectives leave in
        # one order from one thread
        if n_arenas > 1 and not is_multihost():
            self.pool = ThreadPoolExecutor(
                SEED_IN_FLIGHT, thread_name_prefix="racon-seedstream")
        self._scope = metrics.get_scope()

    @staticmethod
    def _alloc(cap: int):
        return (np.empty(cap, np.uint32), np.empty(cap, np.int32),
                np.empty(cap, np.int32), np.empty(cap, np.bool_))

    # ------------------------------------------------------ calling thread

    def feed(self, part) -> None:
        """Pack and launch the next arena; hand the one in front of it
        on."""
        B, L, k, w = self.B, self.L, self.k, self.w
        with obs.span("overlap.seed.pack", rows=len(part)):
            codes, lens, nwin, ids, offs = _pack_arena(part, B, L)
        # chunks arrive in (seq_id, offset) order and a row's slots in
        # position order, so the slices are the canonical table as they
        # lie; anything else is sorted at the end
        key = (ids.astype(np.int64) << 32) | offs
        if key[0] <= self._last_key or not bool(np.all(key[1:] > key[:-1])):
            self.ordered = False
        self._last_key = int(key[-1])
        # packed before the arena in front had been fetched: the stream
        # engaged (never on the calling thread's own fetch)
        ahead = self.front is not None and not self.front.fetched
        if len(self.writing) >= SEED_IN_FLIGHT:
            with obs.span("overlap.seed.fetch"):
                self._settle(SEED_IN_FLIGHT - 1)
        with obs.span("overlap.seed.dispatch", rows=len(part)):
            codes_d = jnp.asarray(codes)
            device_time.submit("h2d", "overlap.seed.put", codes_d)
            # graftlint: disable=jit-shape-hazard (k/w are run-constant flag values — one compile per run; L is the one row length)
            h, strand, sel, nsel = _minimizer_kernel(codes_d, lens, nwin,
                                                     k=k, w=w, L=L)
            device_time.submit("exec", "_minimizer_kernel", nsel,
                               _seed_geometry(B, L, k, w))
        metrics.inc("overlap.seed_arenas")
        metrics.inc("overlap.seed_arenas_ahead", int(ahead))
        metrics.inc("overlap.seed_lanes_total", B * L)
        metrics.inc("overlap.seed_lanes_occupied", int(lens.sum()))
        arena = _Arena(ids, offs, (h, strand, sel, nsel))
        self.arenas.append(arena)
        if self.pool is None:
            with obs.span("overlap.seed.fetch", rows=len(part)):
                self._size(arena)
                arena.count = self._drain(arena)
            return
        if self.front is not None:
            self._hand_over(self.front)
        self.front = arena

    def _reserve(self, n: int) -> int:
        """``n`` entries of the table from the offset returned."""
        if self.used + n > len(self.out[0]):
            self._settle(0)     # no writer holds the arrays that go
            grown = self._alloc(max(self.used + n, min(
                self.limit, len(self.out[0]) * 3 // 2)))
            for dst, src in zip(grown, self.out):
                dst[:self.used] = src[:self.used]
            self.out = grown
        offset = self.used
        self.used += n
        return offset

    def _size(self, arena: _Arena) -> None:
        """``arena``'s slice of the table, from the kernel's selected
        counts (2 KB; the kernel has run)."""
        nsel = fetch_global([arena.device[3]])[0]
        arena.row_sel = nsel[:len(arena.ids)]
        arena.reserved = int(arena.row_sel.sum())
        arena.offset = self._reserve(arena.reserved)

    def _hand_over(self, arena: _Arena) -> None:
        """Size ``arena``'s slice (its kernel ran while the arena behind
        it was packed) and give its planes to a worker."""
        with obs.span("overlap.seed.fetch"):
            self._size(arena)
        self.writing.append((arena, self.pool.submit(self._drain, arena)))

    def _settle(self, keep: int) -> None:
        """Wait until at most ``keep`` arenas are at the workers."""
        while len(self.writing) > keep:
            arena, future = self.writing.popleft()
            arena.count = future.result()

    def _drain(self, arena: _Arena) -> int:
        """Fetch ``arena``'s planes and compact them into its slice
        (a worker, or the calling thread of a one-arena build)."""
        # the metrics scope is thread-local: re-declare the caller's
        metrics.set_scope(self._scope)
        planes = arena.device[:3]
        arena.device = None
        with obs.span("overlap.seed.get"):
            h, strand, sel = fetch_global(list(planes))
        del planes
        arena.fetched = True
        with obs.span("overlap.seed.compact"):
            return _compact_arena(self.out, arena.offset, arena.reserved,
                                  (h, strand, sel), arena.ids, arena.offs,
                                  arena.row_sel)

    # -------------------------------------------------------------- the end

    def finish(self):
        """The table: the last arena handed on, every slice landed, the
        holes closed up in arena order."""
        if self.front is not None:
            self._hand_over(self.front)
            self.front = None
            with obs.span("overlap.seed.fetch"):
                self._settle(0)
        ids, pos = self.out[1], self.out[2]
        end = 0
        for arena in self.arenas:
            offset, count = arena.offset, arena.count
            # a seam between two arenas: the twin ends the slice before
            if (count and end and ids[offset] == ids[end - 1]
                    and pos[offset] == pos[end - 1]):
                offset, count = offset + 1, count - 1
            if count and offset != end:
                for a in self.out:
                    a[end:end + count] = a[offset:offset + count]
            end += count
        table = tuple(a[:end] for a in self.out)
        return table if self.ordered else _canonical(table)

    def close(self) -> None:
        """Retire the workers (a build that failed leaves none behind)."""
        if self.pool is not None:
            self.pool.shutdown(wait=True)


def _canonical(table):
    """``table`` in canonical ``(seq_id, pos)`` order, an entry a key:
    the stable sort (and the repeats it brings together dropped) for
    chunks that did not arrive in ``(seq_id, offset)`` order."""
    h, ids, pos, strand = table
    key = (ids.astype(np.int64) << 32) | pos
    order = np.argsort(key, kind="stable")
    key = key[order]
    uniq = np.ones(key.size, bool)
    uniq[1:] = key[1:] != key[:-1]
    order = order[uniq]
    return h[order], ids[order], pos[order], strand[order]


def build_seed_table(seqs: List[bytes], *, k: int = DEFAULT_K,
                     w: int = DEFAULT_W, cache: bool = False
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                np.ndarray]:
    """The flat minimizer table of a sequence set: parallel numpy arrays
    ``(hash uint32, seq_id int32, pos int32, strand bool)`` in
    deterministic (bucket-grouped, sequence-order) row order, built
    arena by arena as a stream (:class:`_SeedStream`): the full masks
    are fetched and compacted into place on the host.

    ``cache=True`` (the target side of the overlapper) consults the
    fingerprint-keyed table cache first: a hit skips packing, kernels,
    and fetches entirely — counted in ``overlap.cache_hits``."""
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
            return hit
        metrics.inc("overlap.cache_misses")
    chunks = list(_iter_chunks(seqs, k, w))
    if chunks:
        B = SEED_BATCH
        stream = _SeedStream(-(-len(chunks) // B),
                             sum(c[3] for c in chunks), k, w)
        try:
            for begin in range(0, len(chunks), B):
                stream.feed(chunks[begin:begin + B])
            table = stream.finish()
        finally:
            stream.close()
        metrics.inc("overlap.minimizers", int(table[0].size))
    else:
        z = np.zeros(0, np.int32)
        table = (np.zeros(0, np.uint32), z, z, np.zeros(0, bool))
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
