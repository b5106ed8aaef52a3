"""The benchmark's own edit distance between a polished assembly and the
simulator's truth. Nothing here comes from the program: the yardstick
that decides ``residual_ppm`` and ``correct`` may not be a function a
later PR can change.

Two near-identical long sequences are cut at exact shared k-mers
(``anchored_pieces``, copied from ``chip_smoke.py``) and each piece's
distance is found by Landau-Vishkin's O(n*d) diagonal method with a
numpy longest-common-prefix: at a few hundred edits per megabase a
64 kb piece holds about a dozen, so a whole 2 Mbp assembly takes a
fraction of a second. A piece that needs more than ``max_d`` edits
raises :class:`TooFar` — such an assembly has failed whatever the gate.
"""

from __future__ import annotations

import numpy as np

# per-piece cap: 512 edits in 64 kb is 0.8 %, forty times a polished
# assembly's residual; LV is quadratic in the distance, so an unbounded
# call on a broken FASTA would run for minutes
MAX_PIECE_DISTANCE = 512


class TooFar(Exception):
    """Two sequences differ by more than the distance cap, or share no
    anchor where near-identical sequences must."""


def read_fasta(path: str) -> list:
    """[(name, sequence bytes)] of a one-line-per-record FASTA."""
    with open(path, "rb") as fh:
        lines = fh.read().split(b"\n")
    return [(lines[i][1:], lines[i + 1])
            for i in range(0, len(lines) - 1, 2) if lines[i][:1] == b">"]


def _lcp(a: np.ndarray, b: np.ndarray, i: int, j: int) -> int:
    """Length of the common prefix of ``a[i:]`` and ``b[j:]``."""
    n = min(len(a) - i, len(b) - j)
    k, blk = 0, 64
    while k < n:
        m = min(blk, n - k)
        neq = np.flatnonzero(a[i + k:i + k + m] != b[j + k:j + k + m])
        if neq.size:
            return k + int(neq[0])
        k += m
        blk = min(blk * 4, 1 << 16)
    return n


def edit_distance(a: bytes, b: bytes, max_d: int = MAX_PIECE_DISTANCE) -> int:
    """Unit-cost edit distance (Landau-Vishkin); raises :class:`TooFar`
    beyond ``max_d``."""
    x = np.frombuffer(a, np.uint8)
    y = np.frombuffer(b, np.uint8)
    n, m = len(x), len(y)
    if abs(n - m) > max_d:
        raise TooFar(f"lengths {n} and {m} differ by more than {max_d}")
    goal = m - n          # diagonal k = j - i of the end cell
    # far[k] = furthest row i reached on diagonal k with e edits
    far = {0: _lcp(x, y, 0, 0)}
    e = 0
    while far.get(goal, -1) < n:
        e += 1
        if e > max_d:
            raise TooFar(f"more than {max_d} edits")
        nxt = {}
        for k in range(-e, e + 1):
            i = max(far.get(k, -2) + 1,        # substitution
                    far.get(k - 1, -2),        # base of b skipped
                    far.get(k + 1, -2) + 1)    # base of a skipped
            if i < 0:
                continue
            i = min(i, n, m - k)
            if i < 0 or i + k < 0:
                continue
            nxt[k] = i + _lcp(x, y, i, i + k)
        far = nxt
    return e


def anchored_pieces(a: bytes, b: bytes, seg: int = 65536, k: int = 24,
                    slack: int = 4096) -> list:
    """Cut two long, related sequences into pieces at exact shared
    ``k``-mers and return the ``(a piece, b piece)`` list whose edit
    distances sum to the sequences' distance. Anchors are unique exact
    matches within ``slack`` of where the running offset expects them,
    so they lie on the optimal path; sequences under ``2 * seg`` stay
    one piece. Unrelated sequences find no anchor and raise."""
    pieces = []
    ia = ib = 0
    while len(b) - ib > 2 * seg:
        pb = ib + seg
        while True:
            want = ia + (pb - ib)
            lo, hi = max(ia, want - slack), want + slack + k
            pa = a.find(b[pb:pb + k], lo, hi)
            if pa >= 0 and a.find(b[pb:pb + k], pa + 1, hi) < 0:
                break
            pb += k
            if pb > ib + 2 * seg:
                raise TooFar(f"no shared {k}-mer within {seg} bases of "
                             f"offset {ib}: the sequences are not "
                             f"near-identical")
        pieces.append((a[ia:pa], b[ib:pb]))
        ia, ib = pa, pb
    pieces.append((a[ia:], b[ib:]))
    return pieces


def total_distance(fasta_path: str, truth_path: str, seg: int = 65536,
                   max_d: int = MAX_PIECE_DISTANCE) -> tuple:
    """(sum over contigs of the edit distance to the truth, truth
    bases). Contigs pair up by position: the CLI keeps the draft's
    order. ``seg`` is the piece length and ``max_d`` the cap on a
    piece's distance: the defaults suit a polished assembly, a finer
    cut with a higher cap measures a damaged one."""
    got, truth = read_fasta(fasta_path), read_fasta(truth_path)
    if len(got) != len(truth):
        raise TooFar(f"{fasta_path}: {len(got)} contigs, the truth has "
                     f"{len(truth)}")
    dist = sum(edit_distance(pa, pb, max_d)
               for (_, x), (_, t) in zip(got, truth)
               for pa, pb in anchored_pieces(x, t, seg=seg))
    return dist, sum(len(t) for _, t in truth)
