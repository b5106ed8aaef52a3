"""The copied simulator against the original it was copied from."""

import json
import os
import sys

from bench_paths import BENCH, ROOT

import pytest

from harness import simulate


def _traffic(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as fh:
        return json.load(fh)


def test_one_size_reproduces_tools_simulate_byte_for_byte():
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import simulate as original
    want = original.simulate(0.02, seed=11, n_contigs=1)
    got = simulate.simulate(_traffic("tiny20k-30x"), 11)
    assert got == want


def test_contig_sizes_are_honoured_and_seeded():
    traffic = {**_traffic("tiny20k-30x"), "contig_sizes": [9000, 4000, 3000]}
    reads, paf, draft, truths = simulate.simulate(traffic, 2**31 + 5)
    assert [len(t) for t in truths] == [9000, 4000, 3000]
    assert draft.count(b">") == 3
    targets = {line.split(b"\t")[5] for line in paf.splitlines()}
    assert targets == {b"contig_0", b"contig_1", b"contig_2"}
    assert simulate.simulate(traffic, 2**31 + 5)[0] == reads
    assert simulate.simulate(traffic, 2**31 + 6)[0] != reads


def test_a_traffic_file_the_generator_cannot_serve_is_refused():
    import pytest
    with pytest.raises(ValueError, match="PAF"):
        simulate.check_traffic({**_traffic("tiny20k-30x"),
                                "overlaps": "sam"})
    bad = dict(_traffic("tiny20k-30x"))
    del bad["coverage"]
    with pytest.raises(ValueError, match="coverage"):
        simulate.check_traffic(bad)


def test_quantile_lengths_give_every_seed_the_same_sizes():
    traffic = {**_traffic("tiny20k-30x"), "read_len_draw": "quantiles",
               "contig_sizes": [30000, 12000]}

    def read_lengths(seed):
        reads = simulate.simulate(traffic, seed)[1]     # the PAF
        # truth-span lengths are what the traffic fixes; the PAF's target
        # span is that span through the draft's indels, so compare counts
        # and the sorted query lengths' spread instead of bytes
        return sorted(int(line.split(b"\t")[1])
                      for line in reads.splitlines())

    a, b = read_lengths(5), read_lengths(6)
    assert len(a) == len(b)
    # the same spans under other errors: lengths agree to within the
    # indel noise of one read (3 % + 3 %, sd about 20 bases)
    assert max(abs(x - y) for x, y in zip(a, b)) < 150
    normal = {**traffic, "read_len_draw": "normal"}
    c = sorted(int(line.split(b"\t")[1])
               for line in simulate.simulate(normal, 5)[1].splitlines())
    d = sorted(int(line.split(b"\t")[1])
               for line in simulate.simulate(normal, 6)[1].splitlines())
    assert max(abs(x - y) for x, y in zip(c, d)) > 150
    with pytest.raises(ValueError, match="read_len_draw"):
        simulate.check_traffic({**traffic, "read_len_draw": "zipf"})
