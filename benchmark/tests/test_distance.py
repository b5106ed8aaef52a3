"""The benchmark's own edit distance against the textbook recurrence."""

import random

import pytest

from harness import distance


def _dp(a: bytes, b: bytes) -> int:
    prev = list(range(len(b) + 1))
    for i, ca in enumerate(a, 1):
        cur = [i]
        for j, cb in enumerate(b, 1):
            cur.append(min(prev[j] + 1, cur[j - 1] + 1,
                           prev[j - 1] + (ca != cb)))
        prev = cur
    return prev[-1]


def _mutate(rng, seq: bytes, edits: int) -> bytes:
    out = bytearray(seq)
    for _ in range(edits):
        p = rng.randrange(len(out) + 1)
        op = rng.random()
        if op < 0.33 and p < len(out):
            del out[p]
        elif op < 0.66:
            out.insert(p, rng.choice(b"ACGT"))
        elif p < len(out):
            out[p] = rng.choice(b"ACGT")
    return bytes(out)


def test_matches_the_plain_recurrence():
    rng = random.Random(7)
    for _ in range(200):
        a = bytes(rng.choice(b"ACGT") for _ in range(rng.randrange(0, 80)))
        b = _mutate(rng, a, rng.randrange(0, 10))
        assert distance.edit_distance(a, b) == _dp(a, b)


def test_anchored_pieces_sum_to_the_whole():
    rng = random.Random(11)
    a = bytes(rng.choice(b"ACGT") for _ in range(6000))
    b = _mutate(rng, a, 40)
    pieces = distance.anchored_pieces(a, b, seg=1000, slack=200)
    assert len(pieces) > 3
    assert b"".join(p for p, _ in pieces) == a
    assert b"".join(q for _, q in pieces) == b
    assert sum(distance.edit_distance(p, q) for p, q in pieces) == _dp(a, b)


def test_sequences_too_far_apart_raise():
    rng = random.Random(3)
    a = bytes(rng.choice(b"ACGT") for _ in range(400))
    b = bytes(rng.choice(b"ACGT") for _ in range(400))
    with pytest.raises(distance.TooFar):
        distance.edit_distance(a, b, max_d=20)
    with pytest.raises(distance.TooFar):
        distance.anchored_pieces(a * 10, b * 10, seg=500)
