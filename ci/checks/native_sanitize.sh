#!/usr/bin/env bash
# ASan/UBSan smoke over the native host core (graftlint's native half):
# RACON_TPU_NATIVE_SANITIZE=1 rebuilds racon_tpu/native/*.cpp with
# -fsanitize=address,undefined into its own cached .so, then a python
# subprocess — with the ASan runtime preloaded, since CPython itself is
# not ASan-built — exercises the two threaded/streaming paths with the
# ugliest memory behaviour: the bp.cpp thread-pool breaking-points
# decoder, the chunked-inflate gzip sequence parser, the lane-block
# row copier (lanes.cpp: a memcpy per row) and the seed-table compaction
# beside it. Any heap
# overflow / UB the sanitizers see aborts the process (UBSan runs with
# -fno-sanitize-recover), failing this check. Skips cleanly when the
# toolchain has no ASan runtime.
set -e
cd "$(dirname "$0")/../.."

# `|| true`: without g++ the substitution fails under set -e; the
# empty result then takes the SKIP branch like the rest of the repo's
# no-toolchain fallbacks
LIBASAN="$(g++ -print-file-name=libasan.so 2>/dev/null || true)"
if [ -z "$LIBASAN" ] || [ ! -e "$LIBASAN" ]; then
    echo "native sanitize: SKIP (no libasan runtime)"
    exit 0
fi

# leak detection needs ptrace; CPython also "leaks" interned objects at
# exit by design — this smoke is after overflows/UB, not exit leaks
export ASAN_OPTIONS="detect_leaks=0:abort_on_error=1"
export RACON_TPU_NATIVE_SANITIZE=1

LD_PRELOAD="$LIBASAN" python - <<'PY'
import pathlib
import sys

from racon_tpu import native

path = native.build(force=True)
assert path.name == "libracon_native_san.so", path
assert native.available(), "sanitized native library failed to load"

# 1) bp.cpp: the thread-pool breaking-points decoder (threaded writes
#    into one shared columnar output buffer at per-overlap offsets)
cigars = ["5M2I3M1D10M", "20M", "", "3M1I1D3M" * 40, "7M"] * 50
n = len(cigars)
arrs = native.bp_from_cigar_batch(
    cigars, [0] * n, [0] * n,
    [sum(int(c[:-1]) for c in __import__("re").findall(r"\d+[MD]", s))
     for s in cigars],
    5, num_threads=4)
assert len(arrs) == n and arrs[0].shape[1] == 4
print("bp thread-pool decoder under ASan/UBSan: ok", file=sys.stderr)

# 2) parsers.cpp: the streaming chunked-inflate gzip path (bounded
#    rolling buffer refills across chunk boundaries)
import gzip
import tempfile

with tempfile.NamedTemporaryFile(suffix=".fastq.gz", delete=False) as f:
    tmp = f.name
    long_seq = b"ACGT" * 50000  # forces multi-chunk inflate + long lines
    with gzip.open(f, "wb") as gz:
        for i in range(20):
            gz.write(b"@r%d\n" % i + long_seq + b"\n+\n"
                     + b"9" * len(long_seq) + b"\n")
recs = native.parse_seqfile(tmp, True)
assert len(recs) == 20 and recs[0][1] == long_seq
pathlib.Path(tmp).unlink()
print("streaming gzip parser under ASan/UBSan: ok", file=sys.stderr)

# 3) lanes.cpp: the consensus lane-block row copier (a memcpy per row):
#    a row that ends at the pool's last lane, one cut at Lq, an empty
#    one sitting at len(pool), and the last row of the block
import numpy as np

pool = np.arange(1000, dtype=np.uint16)
out = np.zeros((4, 64), np.uint16)
native.copy_lane_rows(pool, np.array([990, 0, 1000, 936]),
                      np.array([10, 500, 0, 64]), np.array([3, 0, 1, 2]),
                      out)
assert out[3, :10].tolist() == list(range(990, 1000))
assert not out[3, 10:].any() and not out[1].any()
assert out[0].tolist() == list(range(64)) and out[2, -1] == 999
print("lane-block row copier under ASan/UBSan: ok", file=sys.stderr)

# 4) lanes.cpp: the seed-table compaction (eight mask bytes a load): a
#    row width that is no multiple of eight, the last slot of the last
#    row selected, a seam repeat, and a slice that ends at the table's
#    last entry
P = 13
sel = np.zeros((3, P), bool)
sel[0, [0, 12]] = sel[1, [2, 9]] = sel[2, P - 1] = True
h = np.arange(3 * P, dtype=np.uint32).reshape(3, P)
table = (np.zeros(6, np.uint32), np.zeros(6, np.int32),
         np.zeros(6, np.int32), np.zeros(6, bool))
n = native.compact_seed_rows(
    h, sel, sel.copy(), np.array([7, 7, 8]), np.array([0, 10, 0]),
    sel.sum(1), table, 1, 5)
# row 1's slot 2 is position 12 of sequence 7 again: dropped
assert n == 4 and table[2][1:5].tolist() == [0, 12, 19, 12]
assert table[0][1:5].tolist() == [0, 12, 22, 38] and table[1][4] == 8
print("seed-table compaction under ASan/UBSan: ok", file=sys.stderr)
PY

echo "native sanitize: OK"
