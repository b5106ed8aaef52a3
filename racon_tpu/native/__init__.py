"""Native host core: C++ aligner (and later POA) loaded via ctypes.

Built on demand with g++ (no pip/pybind11 dependency); the shared object is
cached next to the sources and rebuilt when any .cpp is newer.

``RACON_TPU_NATIVE_SANITIZE=1`` selects an ASan/UBSan build instead
(``-fsanitize=address,undefined``, separate cached .so): the CI smoke
``ci/checks/native_sanitize.sh`` runs the bp.cpp thread-pool decoder, the
streaming gzip parser and the lanes.cpp row copier under it. Loading the
sanitized object needs the ASan runtime preloaded (``LD_PRELOAD=$(g++
-print-file-name=libasan.so)``), so the variant is chosen per process at
first load.
"""

from __future__ import annotations

import ctypes
import os
import pathlib
import subprocess
import threading

from .. import flags as _flags
from ..utils.logger import log_swallowed as _log_swallowed

_DIR = pathlib.Path(__file__).resolve().parent
_LIB_PATH = _DIR / "libracon_native.so"
_LIB_SAN_PATH = _DIR / "libracon_native_san.so"
_EXT_PATH = _DIR / "racon_native_ext.so"
# pyext.cpp is the optional CPython extension (needs Python headers) —
# built separately so the ctypes core never depends on them
_SOURCES = sorted(s for s in _DIR.glob("*.cpp") if s.name != "pyext.cpp")
_EXT_SOURCES = [_DIR / "pyext.cpp", _DIR / "parsers.cpp"]
_lock = threading.Lock()
_lib = None
_ext = None
_ext_tried = False


class NativeBuildError(RuntimeError):
    pass


def _sanitize_build() -> bool:
    """ASan/UBSan build mode (RACON_TPU_NATIVE_SANITIZE=1)."""
    return _flags.get_bool("RACON_TPU_NATIVE_SANITIZE")


def _lib_path() -> pathlib.Path:
    return _LIB_SAN_PATH if _sanitize_build() else _LIB_PATH


def _needs_build() -> bool:
    path = _lib_path()
    if not path.exists():
        return True
    lib_mtime = path.stat().st_mtime
    return any(src.stat().st_mtime > lib_mtime for src in _SOURCES)


def _compile(cmd, out: pathlib.Path) -> subprocess.CompletedProcess:
    """Run the g++ command ``cmd`` onto ``out`` atomically: compile to a
    per-process temporary name, then rename — a concurrent process
    never loads a half-written object, and a forced rebuild under a
    running loader is safe."""
    tmp = out.with_name(f".{out.name}.{os.getpid()}.tmp")
    proc = subprocess.run([*cmd, "-o", str(tmp)], capture_output=True,
                          text=True)
    if proc.returncode == 0:
        os.replace(tmp, out)
    else:
        tmp.unlink(missing_ok=True)
    return proc


def build(force: bool = False) -> pathlib.Path:
    """Compile the native library if needed (``force``: always, and the
    optional parser extension with it — a .so copied in from another
    machine was built ``-march=native`` for that machine's CPU, and its
    mtime says nothing). Returns the library's path. The sanitized
    variant keeps frame pointers and -O1 so ASan/UBSan reports carry
    usable stacks; it caches to its own .so, so the fast build is never
    evicted by a sanitizer run."""
    path = _lib_path()
    with _lock:
        if force or _needs_build():
            if _sanitize_build():
                opt = ["-O1", "-g", "-fno-omit-frame-pointer",
                       "-fsanitize=address,undefined",
                       "-fno-sanitize-recover=undefined"]
            else:
                opt = ["-O3", "-march=native"]
            cmd = [
                "g++", *opt, "-std=c++17", "-shared", "-fPIC",
                "-pthread",
                *[str(s) for s in _SOURCES], "-lz",
            ]
            # serializing the compile IS this lock's purpose: two
            # threads racing g++ onto one .so would tear the artifact
            # graftlint: disable=blocking-under-lock (the lock exists to serialize the one-time compile onto one .so)
            proc = _compile(cmd, path)
            if proc.returncode != 0:
                raise NativeBuildError(
                    f"native build failed:\n{proc.stderr[-4000:]}")
        if force:
            _EXT_PATH.unlink(missing_ok=True)  # load_ext() rebuilds it
    return path


def load_ext():
    """Build/load the optional CPython extension (fast overlap-record
    materialization); returns the module or None. Never raises — the
    ctypes path is the functional fallback."""
    global _ext, _ext_tried
    if _ext_tried:
        return _ext
    with _lock:
        if _ext_tried:
            return _ext
        _ext_tried = True
        try:
            import sysconfig

            newest = max(s.stat().st_mtime for s in _EXT_SOURCES)
            if not _EXT_PATH.exists() or \
                    _EXT_PATH.stat().st_mtime < newest:
                cmd = [
                    "g++", "-O3", "-std=c++17", "-shared", "-fPIC",
                    "-march=native",
                    f"-I{sysconfig.get_paths()['include']}",
                    *[str(s) for s in _EXT_SOURCES], "-lz",
                ]
                # graftlint: disable=blocking-under-lock (the lock exists to serialize the one-time compile onto one .so)
                proc = _compile(cmd, _EXT_PATH)
                if proc.returncode != 0:
                    return None
            import importlib.machinery
            import importlib.util

            loader = importlib.machinery.ExtensionFileLoader(
                "racon_native_ext", str(_EXT_PATH))
            spec = importlib.util.spec_from_loader("racon_native_ext",
                                                   loader)
            _ext = importlib.util.module_from_spec(spec)
            loader.exec_module(_ext)
        except Exception as e:
            _log_swallowed("native: CPython extension build/load failed "
                           "(ctypes parser fallback in use)", e)
            _ext = None
    return _ext


def load():
    """Load (building if necessary) and return the ctypes library handle,
    or None when no C++ toolchain is available. The variant (plain vs
    ASan/UBSan) is fixed at the first successful load of this process."""
    global _lib
    if _lib is not None:
        return _lib
    with _lock:
        if _lib is not None:
            return _lib
    try:
        build()
    except (NativeBuildError, FileNotFoundError) as e:
        _log_swallowed("native: core library unavailable (Python/host "
                       "fallbacks in use)", e)
        return None
    try:
        lib = ctypes.CDLL(str(_lib_path()))
    except OSError as e:
        if _sanitize_build():
            # dlopen of an ASan-instrumented .so into a non-ASan python
            # fails unless the runtime is preloaded — name the fix
            # instead of dying with a bare dlopen error (the CI smoke
            # ci/checks/native_sanitize.sh sets this up)
            raise NativeBuildError(
                "loading the RACON_TPU_NATIVE_SANITIZE build requires "
                "the ASan runtime preloaded: run under LD_PRELOAD="
                '"$(g++ -print-file-name=libasan.so)" '
                f"(dlopen said: {e})") from e
        _log_swallowed("native: core library failed to load "
                       "(Python/host fallbacks in use)", e)
        return None
    lib.rt_nw_cigar.restype = ctypes.c_void_p
    lib.rt_nw_cigar.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                                ctypes.c_char_p, ctypes.c_int64]
    lib.rt_edit_distance.restype = ctypes.c_int64
    lib.rt_edit_distance.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                                     ctypes.c_char_p, ctypes.c_int64]
    lib.rt_nw_cigar_batch.restype = None
    lib.rt_nw_cigar_batch.argtypes = [
        ctypes.c_int64,
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64, ctypes.POINTER(ctypes.c_void_p)]
    lib.rt_poa_consensus_batch.restype = None
    lib.rt_poa_consensus_batch.argtypes = [
        ctypes.c_int64, ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_char_p), ctypes.POINTER(ctypes.c_uint8),
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_uint8), ctypes.c_int32,
        ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_uint8)]
    lib.rt_free.restype = None
    lib.rt_free.argtypes = [ctypes.c_void_p]
    lib.rt_parse_seqfile.restype = ctypes.c_int64
    lib.rt_parse_seqfile.argtypes = [
        ctypes.c_char_p, ctypes.c_int32,
        ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_void_p),
        ctypes.c_char_p]
    lib.rt_parse_ovlfile.restype = ctypes.c_int64
    lib.rt_parse_ovlfile.argtypes = [
        ctypes.c_char_p, ctypes.c_int32,
        ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_void_p),
        ctypes.POINTER(ctypes.c_void_p), ctypes.c_char_p]
    lib.rt_bp_from_cigar_batch.restype = None
    lib.rt_bp_from_cigar_batch.argtypes = [
        ctypes.c_int64, ctypes.POINTER(ctypes.c_char_p),
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int64, ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int64)]
    lib.rt_copy_lane_rows.restype = None
    lib.rt_copy_lane_rows.argtypes = [
        ctypes.c_int64, ctypes.POINTER(ctypes.c_uint16),
        ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
        ctypes.POINTER(ctypes.c_uint16)]
    lib.rt_copy_byte_rows.restype = None
    lib.rt_copy_byte_rows.argtypes = [
        ctypes.c_int64, ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64, ctypes.POINTER(ctypes.c_uint8)]
    lib.rt_compact_seed_rows.restype = ctypes.c_int64
    lib.rt_compact_seed_rows.argtypes = [
        ctypes.c_int64, ctypes.c_int64, ctypes.POINTER(ctypes.c_uint32),
        ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_uint8),
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int64,
        ctypes.POINTER(ctypes.c_uint32), ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_uint8)]
    _lib = lib
    return _lib


def available() -> bool:
    return load() is not None


def nw_cigar(q: bytes, t: bytes) -> str:
    """Global unit-cost alignment; returns CIGAR (M/I/D, I consumes query)."""
    lib = load()
    if lib is None:
        raise NativeBuildError("native library unavailable")
    ptr = lib.rt_nw_cigar(q, len(q), t, len(t))
    try:
        return ctypes.string_at(ptr).decode()
    finally:
        lib.rt_free(ptr)


def edit_distance(a: bytes, b: bytes) -> int:
    lib = load()
    if lib is None:
        raise NativeBuildError("native library unavailable")
    return lib.rt_edit_distance(a, len(a), b, len(b))


def poa_consensus_batch(windows, trim: bool, match: int, mismatch: int,
                        gap: int, num_threads: int = 1) -> list:
    """Spoa-semantics consensus for a batch of Window objects on the C++
    thread pool (host analog of the reference's per-window futures,
    src/polisher.cpp:490-503). Returns ``[(consensus bytes, polished,
    failed), ...]``; ``failed`` windows should fall back to the Python
    engine."""
    lib = load()
    if lib is None:
        raise NativeBuildError("native library unavailable")
    nw = len(windows)
    if nw == 0:
        return []

    first = [0]
    seqs, lens, quals, has_qual, begins, ends = [], [], [], [], [], []
    ids, ranks, is_tgs = [], [], []
    from ..core.window import WindowType
    for w in windows:
        for i, seq in enumerate(w.sequences):
            seqs.append(seq)
            lens.append(len(seq))
            q = w.qualities[i]
            quals.append(q if q is not None else b"")
            has_qual.append(1 if q is not None else 0)
            b, e = w.positions[i]
            begins.append(b)
            ends.append(e)
        first.append(len(seqs))
        ids.append(w.id)
        ranks.append(w.rank)
        is_tgs.append(1 if w.type == WindowType.TGS else 0)

    ns = len(seqs)
    c_first = (ctypes.c_int64 * (nw + 1))(*first)
    c_seqs = (ctypes.c_char_p * ns)(*seqs)
    c_lens = (ctypes.c_int64 * ns)(*lens)
    c_quals = (ctypes.c_char_p * ns)(*quals)
    c_hasq = (ctypes.c_uint8 * ns)(*has_qual)
    c_begins = (ctypes.c_int64 * ns)(*begins)
    c_ends = (ctypes.c_int64 * ns)(*ends)
    c_ids = (ctypes.c_int64 * nw)(*ids)
    c_ranks = (ctypes.c_int64 * nw)(*ranks)
    c_tgs = (ctypes.c_uint8 * nw)(*is_tgs)
    c_out = (ctypes.c_void_p * nw)()
    c_outlen = (ctypes.c_int64 * nw)()
    c_pol = (ctypes.c_uint8 * nw)()
    c_status = (ctypes.c_uint8 * nw)()

    lib.rt_poa_consensus_batch(
        nw, c_first, c_seqs, c_lens, c_quals, c_hasq, c_begins, c_ends,
        c_ids, c_ranks, c_tgs, 1 if trim else 0, match, mismatch, gap,
        num_threads, c_out, c_outlen, c_pol, c_status)

    result = []
    for i in range(nw):
        if c_out[i]:  # null under native OOM -> failed flag drives fallback
            data = ctypes.string_at(c_out[i], c_outlen[i])
            lib.rt_free(c_out[i])
        else:
            data = b""
        result.append((data, bool(c_pol[i]), bool(c_status[i])))
    return result


def nw_cigar_batch(pairs, num_threads: int = 1) -> list:
    """Align many (q, t) byte-string pairs in parallel (C++ thread pool,
    dynamic work queue — the host analog of the reference's per-batch
    fill/process loop at src/cuda/cudapolisher.cpp:98-160)."""
    lib = load()
    if lib is None:
        raise NativeBuildError("native library unavailable")
    count = len(pairs)
    if count == 0:
        return []
    qs = (ctypes.c_char_p * count)(*[q for q, _ in pairs])
    ts = (ctypes.c_char_p * count)(*[t for _, t in pairs])
    qns = (ctypes.c_int64 * count)(*[len(q) for q, _ in pairs])
    tns = (ctypes.c_int64 * count)(*[len(t) for _, t in pairs])
    outs = (ctypes.c_void_p * count)()
    lib.rt_nw_cigar_batch(count, qs, qns, ts, tns, num_threads, outs)
    result = []
    for i in range(count):
        result.append(ctypes.string_at(outs[i]).decode())
        lib.rt_free(outs[i])
    return result


def bp_from_cigar_batch(cigars, q_offs, t_begins, t_ends,
                        window_length: int, num_threads: int = 1) -> list:
    """Decode many CIGARs into per-window breaking-point rows
    (t_first, q_first, t_end_excl, q_end_excl) on the C++ thread pool.
    Returns one int32 ndarray of shape (k, 4) per CIGAR, row-identical to
    the Python walker ``core.overlap.breaking_points_from_cigar``. The
    per-overlap arrays are views into one flat columnar buffer, so the
    whole batch costs a single allocation."""
    import numpy as np

    lib = load()
    if lib is None:
        raise NativeBuildError("native library unavailable")
    count = len(cigars)
    if count == 0:
        return []
    enc = [c.encode() if isinstance(c, str) else (c or b"")
           for c in cigars]
    c_cigars = (ctypes.c_char_p * count)(*enc)
    qo = np.ascontiguousarray(q_offs, dtype=np.int64)
    tb = np.ascontiguousarray(t_begins, dtype=np.int64)
    te = np.ascontiguousarray(t_ends, dtype=np.int64)
    w = int(window_length)
    # capacity per overlap = its window-boundary count (multiples of w in
    # (t_begin, t_end), plus the final t_end-1 boundary)
    caps = np.maximum(0, (np.maximum(te, 1) - 1) // w - tb // w) + 1
    offs = np.zeros(count + 1, dtype=np.int64)
    np.cumsum(caps, out=offs[1:])
    out = np.empty(int(offs[-1]) * 4, dtype=np.int32)
    counts = np.zeros(count, dtype=np.int64)
    i64p = ctypes.POINTER(ctypes.c_int64)
    lib.rt_bp_from_cigar_batch(
        count, c_cigars,
        qo.ctypes.data_as(i64p), tb.ctypes.data_as(i64p),
        te.ctypes.data_as(i64p), w, num_threads,
        offs.ctypes.data_as(i64p),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        counts.ctypes.data_as(i64p))
    return [out[int(offs[i]) * 4: (int(offs[i]) + int(counts[i])) * 4]
            .reshape(-1, 4) for i in range(count)]


def copy_lane_rows(pool, src, length, dest, out) -> None:
    """Write the consensus lane block by row copies: for row ``r``,
    ``min(length[r], Lq)`` uint16 lanes from ``pool[src[r]:]`` into
    ``out[dest[r]]`` (``out`` a C-contiguous ``[B, Lq]`` uint16 block the
    caller zeroed: lanes past a row's length are left alone). A row that
    reaches outside the pool or the block raises IndexError before
    anything is written, as the fancy-indexed numpy gather did."""
    import numpy as np

    lib = load()
    if lib is None:
        raise NativeBuildError("native library unavailable")
    if (pool.dtype != np.uint16 or out.dtype != np.uint16 or out.ndim != 2
            or not pool.flags.c_contiguous or not out.flags.c_contiguous):
        raise ValueError("copy_lane_rows wants C-contiguous uint16 arrays")
    src = np.ascontiguousarray(src, dtype=np.int64)
    dest = np.ascontiguousarray(dest, dtype=np.int64)
    length = np.minimum(np.asarray(length, np.int64), out.shape[1])
    count = len(src)
    if not len(length) == len(dest) == count:
        raise ValueError("copy_lane_rows: one src, length and dest a row")
    if not count:
        return
    if (src.min() < 0 or length.min() < 0
            or (src + length).max() > len(pool)
            or dest.min() < 0 or dest.max() >= out.shape[0]):
        raise IndexError("lane row outside the pool or the block")
    i64p = ctypes.POINTER(ctypes.c_int64)
    u16p = ctypes.POINTER(ctypes.c_uint16)
    lib.rt_copy_lane_rows(
        count, pool.ctypes.data_as(u16p), src.ctypes.data_as(i64p),
        length.ctypes.data_as(i64p), dest.ctypes.data_as(i64p),
        out.shape[1], out.ctypes.data_as(u16p))


def copy_byte_rows(pool: bytes, length, out) -> None:
    """Write the aligner's sequence block by row copies: ``pool`` holds
    the rows' bytes back to back, row ``r`` (``length[r]`` of them) goes
    to the head of ``out[r]`` (``out`` a C-contiguous ``[rows, row_len]``
    uint8 block the caller zeroed). A row longer than ``row_len`` or a
    pool of another size than the lengths' sum raises before anything
    is written."""
    import numpy as np

    lib = load()
    if lib is None:
        raise NativeBuildError("native library unavailable")
    if out.dtype != np.uint8 or out.ndim != 2 or not out.flags.c_contiguous:
        raise ValueError("copy_byte_rows wants a C-contiguous uint8 block")
    length = np.ascontiguousarray(length, dtype=np.int64)
    count = len(length)
    if not count:
        return
    if count > out.shape[0] or length.min() < 0 \
            or length.max() > out.shape[1] or int(length.sum()) != len(pool):
        raise IndexError("byte row outside the pool or the block")
    lib.rt_copy_byte_rows(
        count, pool, length.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        out.shape[1], out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))


def compact_seed_rows(h, sel, strand, row_id, row_off, row_sel, out,
                      offset: int, cap: int) -> int:
    """Write a minimizer arena's selected slots into the seed table:
    rows ``[0, len(row_id))`` of the C-contiguous ``[B, P]`` planes
    ``h`` (uint32), ``sel`` and ``strand`` (bool) are walked in
    row-major order and every selected slot lands as ``(hash,
    row_id[r], row_off[r] + column, strand)`` in the four arrays of
    ``out`` (uint32, int32, int32, bool) from ``offset`` on, an entry
    equal in ``(id, pos)`` to the one before it dropped (a slice seam's
    repeat). ``row_sel`` is the selected count a row; ``cap`` entries
    at most are written. Returns how many were."""
    import numpy as np

    lib = load()
    if lib is None:
        raise NativeBuildError("native library unavailable")
    rows = len(row_id)
    planes = ((h, np.uint32), (sel, np.bool_), (strand, np.bool_))
    if any(a.dtype != t or a.ndim != 2 or a.shape != h.shape
           or not a.flags.c_contiguous for a, t in planes):
        raise ValueError("compact_seed_rows wants C-contiguous [B, P] "
                         "uint32 / bool / bool planes of one shape")
    meta = [np.ascontiguousarray(a, dtype=np.int32)
            for a in (row_id, row_off, row_sel)]
    if rows > h.shape[0] or any(len(a) != rows for a in meta):
        raise ValueError("compact_seed_rows: one id, offset and count a "
                         "row, at most the planes' rows")
    outs = ((out[0], np.uint32), (out[1], np.int32), (out[2], np.int32),
            (out[3], np.bool_))
    if any(a.dtype != t or a.ndim != 1 or not a.flags.c_contiguous
           for a, t in outs):
        raise ValueError("compact_seed_rows wants contiguous uint32 / "
                         "int32 / int32 / bool output arrays")
    if offset < 0 or cap < 0 or any(offset + cap > len(a) for a, _ in outs):
        raise IndexError("seed slice outside the table")
    if not rows:
        return 0
    u8p = ctypes.POINTER(ctypes.c_uint8)
    i32p = ctypes.POINTER(ctypes.c_int32)
    u32p = ctypes.POINTER(ctypes.c_uint32)

    def at(a, ptr):
        return ctypes.cast(a.ctypes.data + offset * a.itemsize, ptr)

    n = lib.rt_compact_seed_rows(
        rows, h.shape[1], h.ctypes.data_as(u32p), sel.ctypes.data_as(u8p),
        strand.ctypes.data_as(u8p), *(a.ctypes.data_as(i32p) for a in meta),
        cap, at(out[0], u32p), at(out[1], i32p), at(out[2], i32p),
        at(out[3], u8p))
    if n < 0:
        raise ValueError("compact_seed_rows: more selected slots than the "
                         "rows' counts sum to")
    return n


def parse_seqfile(path: str, is_fastq: bool):
    """Parse a (possibly gzipped) FASTA/FASTQ file natively; returns a
    list of (name, data, quality|None) byte tuples. Raises ValueError on
    malformed input (same conditions as the Python parsers). Prefers
    the CPython extension (the tuples built in C); the ctypes route
    below is the fallback."""
    ext = load_ext()
    if ext is not None:
        return ext.parse_seqfile(path, is_fastq)
    lib = load()
    if lib is None:
        raise NativeBuildError("native library unavailable")
    blob = ctypes.c_void_p()
    offs = ctypes.c_void_p()
    err = ctypes.create_string_buffer(256)
    n = lib.rt_parse_seqfile(path.encode(), 1 if is_fastq else 0,
                             ctypes.byref(blob), ctypes.byref(offs), err)
    if n < 0:
        raise ValueError(err.value.decode(errors="replace"))
    try:
        o = (ctypes.c_int64 * (6 * n)).from_address(offs.value) if n else []
        base = blob.value
        out = []
        for i in range(n):
            no, nl, so, sl, qo, ql = o[6 * i: 6 * i + 6]
            out.append((
                ctypes.string_at(base + no, nl),
                ctypes.string_at(base + so, sl),
                ctypes.string_at(base + qo, ql) if qo >= 0 else None,
            ))
        return out
    finally:
        if n >= 0:
            lib.rt_free(blob)
            lib.rt_free(offs)


# per-format (n_strings, n_nums) arity of rt_parse_ovlfile records
_OVL_ARITY = {0: (2, 7), 1: (0, 12), 2: (3, 2)}


def parse_ovlfile(path: str, fmt: int):
    """Parse a (possibly gzipped) overlap file natively: fmt 0=PAF,
    1=MHAP, 2=SAM. Returns a list of records with ``.fmt``/``.fields``
    attributes, the fields identical to the Python oracle parsers'
    ``OverlapRecord.fields`` (io/parsers.py). Prefers the CPython
    extension (record materialization in C, >100 MB/s); the ctypes
    route below is the fallback."""
    ext = load_ext()
    if ext is not None:
        return ext.parse_ovlfile(path, fmt)
    lib = load()
    if lib is None:
        raise NativeBuildError("native library unavailable")
    blob = ctypes.c_void_p()
    soffs = ctypes.c_void_p()
    nums = ctypes.c_void_p()
    err = ctypes.create_string_buffer(256)
    n = lib.rt_parse_ovlfile(path.encode(), fmt, ctypes.byref(blob),
                             ctypes.byref(soffs), ctypes.byref(nums), err)
    if n < 0:
        raise ValueError(err.value.decode(errors="replace"))
    ns, nn = _OVL_ARITY[fmt]
    from ..io.parsers import OverlapRecord
    fmt_name = ("paf", "mhap", "sam")[fmt]
    try:
        so = ((ctypes.c_int64 * (2 * ns * n)).from_address(soffs.value)
              if n and ns else [])
        nu = ((ctypes.c_double * (nn * n)).from_address(nums.value)
              if n else [])
        base = blob.value
        out = []
        for i in range(n):
            strs = [ctypes.string_at(base + so[2 * (ns * i + k)],
                                     so[2 * (ns * i + k) + 1])
                    for k in range(ns)]
            num = nu[nn * i: nn * i + nn]
            if fmt == 0:
                b = int(num[3])
                f = (strs[0], int(num[0]), int(num[1]), int(num[2]),
                     chr(b) if b else "", strs[1], int(num[4]),
                     int(num[5]), int(num[6]))
            elif fmt == 1:
                f = (int(num[0]), int(num[1]), num[2], int(num[3]),
                     int(num[4]), int(num[5]), int(num[6]),
                     int(num[7]), int(num[8]), int(num[9]),
                     int(num[10]), int(num[11]))
            else:
                f = (strs[0], int(num[0]), strs[1], int(num[1]), strs[2])
            out.append(OverlapRecord(fmt_name, f))
        return out
    finally:
        if n >= 0:
            lib.rt_free(blob)
            lib.rt_free(soffs)
            lib.rt_free(nums)
