"""The consensus feed's lane block, written once by row copies.

- ``LayerStore.gather_qpw`` (the native row copier, and the per-row
  numpy path where the native core is absent) against the formula it
  replaced, kept here as the oracle: the ``[rows, Lq]`` index-matrix
  gather;
- ``TpuPoaConsensus._pack_shard``'s six pair arrays and five window
  arrays against the same packer over that oracle, and what
  ``_launch_group_impl`` puts on the device against both;
- one whole ``cli.main`` job held to ONE device (the ragged stream both
  benchmark cells run) against the same job on the mesh path's padded
  packer: the same FASTA, every lane row copied natively.
"""

import io
import json
import sys

import numpy as np
import pytest

from racon_tpu import cli, native
from racon_tpu.core.layers import LayerStore
from racon_tpu.core.window import Window, WindowType
from racon_tpu.obs import metrics, trace
from racon_tpu.ops import poa


def oracle_gather(store, rows, Lq):
    """The gather ``LayerStore.gather_qpw`` was until PR 35, verbatim."""
    lens = store.length[rows]
    pos = np.arange(Lq, dtype=np.int64)[None, :]
    valid = pos < lens[:, None]
    srcs = (store.src[rows][:, None]
            + np.minimum(pos, np.maximum(lens[:, None] - 1, 0)))
    return np.where(valid, store.qpw_pool[srcs], 0).astype(np.uint16)


def make_store(seed, lengths, pool_len=None, tail_row=None):
    """A store over a random lane pool whose rows have ``lengths``, laid
    at random offsets; ``tail_row`` ends at the pool's last lane."""
    rng = np.random.default_rng(seed)
    lengths = np.asarray(lengths, np.int64)
    pool_len = pool_len or int(lengths.max(initial=1)) * 3 + 17
    # lanes as the packer makes them: weight <= 93 in the high bits, a
    # code 0..4 in the low three (weight >= 1 here: no real lane reads 0)
    qpw_pool = ((rng.integers(1, 94, pool_len) << 3)
                | rng.integers(0, 5, pool_len)).astype(np.uint16)
    src = rng.integers(0, pool_len - lengths + 1)
    if tail_row is not None:
        src[tail_row] = pool_len - lengths[tail_row]
    k = len(lengths)
    begin = rng.integers(0, 40, k)
    end = begin + rng.integers(30, 60, k)
    raw = np.zeros(pool_len, np.uint8)
    return LayerStore(raw, raw, qpw_pool, src.astype(np.int64), lengths,
                      begin.astype(np.int64), end.astype(np.int64),
                      np.zeros(k, np.int64), np.ones(k, bool),
                      np.array([0, k]))


@pytest.fixture(params=["native", "numpy"])
def copier(request, monkeypatch):
    """Both ways the block is written: the native copier, and the
    fallback that runs where the native core is not available."""
    if request.param == "numpy":
        monkeypatch.setattr(native, "available", lambda: False)

        def refuse(*a, **k):
            raise AssertionError("native copier called without a core")
        monkeypatch.setattr(native, "copy_lane_rows", refuse)
    else:
        assert native.available()
    return request.param


LQ = 64
GATHER_CASES = {
    # name: (lengths, rows picked, tail_row)
    "ragged": ([5, 64, 1, 33, 63, 17, 40], None, None),
    "zero_length_row": ([12, 0, 30, 0], None, None),
    "row_longer_than_Lq": ([10, 65, 200, 64], None, None),
    "row_ends_at_the_pools_last_lane": ([20, 31, 9], None, 1),
    "long_row_at_the_pools_end": ([20, 150], None, 1),
    "picked_and_repeated_rows": ([7, 50, 22, 64, 3], [4, 1, 1, 3], None),
}


@pytest.mark.parametrize("case", sorted(GATHER_CASES))
def test_gather_equals_the_index_matrix_gather(case, copier):
    lengths, rows, tail = GATHER_CASES[case]
    store = make_store(7, lengths, tail_row=tail)
    rows = np.arange(store.n_rows) if rows is None else np.asarray(rows)
    block = store.gather_qpw(rows, LQ)
    assert block.dtype == np.uint16 and block.shape == (len(rows), LQ)
    assert block.flags.c_contiguous
    assert np.array_equal(block, oracle_gather(store, rows, LQ))


def test_permuted_dest_writes_each_row_where_it_was_sent(copier):
    store = make_store(11, [9, 64, 0, 40, 100, 1])
    rows = np.arange(store.n_rows)
    dest = np.array([7, 2, 5, 0, 9, 4])
    out = np.zeros((12, LQ), np.uint16)
    assert store.gather_qpw(rows, LQ, out=out, dest=dest) is out
    want = np.zeros((12, LQ), np.uint16)
    want[dest] = oracle_gather(store, rows, LQ)
    assert np.array_equal(out, want)


def test_rows_of_two_stores_share_one_block(copier):
    a = make_store(3, [30, 5, 64, 12])
    b = make_store(4, [64, 1, 70], tail_row=2)
    out = np.zeros((8, LQ), np.uint16)
    dest_a, dest_b = np.array([0, 3, 4, 6]), np.array([5, 1, 2])
    a.gather_qpw(np.arange(4), LQ, out=out, dest=dest_a)
    b.gather_qpw(np.arange(3), LQ, out=out, dest=dest_b)
    want = np.zeros((8, LQ), np.uint16)
    want[dest_a] = oracle_gather(a, np.arange(4), LQ)
    want[dest_b] = oracle_gather(b, np.arange(3), LQ)
    assert np.array_equal(out, want)
    assert not out[7].any()         # a row nobody was sent to stays 0


def test_empty_row_past_the_pools_end_copies_nothing(copier):
    """A row of length 0 may sit at ``len(pool)``: nothing is read."""
    store = make_store(5, [8, 0])
    store.src[1] = len(store.qpw_pool)
    block = store.gather_qpw(np.arange(2), LQ)
    assert np.array_equal(block[0], oracle_gather(store, [0], LQ)[0])
    assert not block[1].any()
    assert store.gather_qpw(np.arange(0), LQ).shape == (0, LQ)


@pytest.mark.parametrize("fault", ["src_past_pool", "negative_src",
                                   "dest_past_block", "negative_dest"])
def test_native_copier_refuses_a_row_outside_its_arrays(fault):
    """The index-matrix gather raised IndexError on such a row; a memcpy
    would not, so the wrapper checks before anything is written."""
    pool = np.arange(100, dtype=np.uint16)
    src, length, dest = (np.array([0, 90]), np.array([10, 10]),
                         np.array([0, 1]))
    if fault == "src_past_pool":
        src[1] = 91
    elif fault == "negative_src":
        src[0] = -1
    elif fault == "dest_past_block":
        dest[1] = 2
    else:
        dest[0] = -1
    out = np.zeros((2, 16), np.uint16)
    with pytest.raises(IndexError):
        native.copy_lane_rows(pool, src, length, dest, out)
    assert not out.any()


def test_native_copier_wants_the_packers_arrays():
    pool = np.arange(100, dtype=np.uint16)
    one = np.array([0])
    with pytest.raises(ValueError):
        native.copy_lane_rows(pool, one, one, one,
                              np.zeros((2, 16), np.uint32))
    with pytest.raises(ValueError):     # a strided view of a wider block
        native.copy_lane_rows(pool, one, one, one,
                              np.zeros((2, 32), np.uint16)[:, :16])
    with pytest.raises(ValueError):
        native.copy_lane_rows(pool, one, np.array([1, 2]), one,
                              np.zeros((2, 16), np.uint16))


# ----------------------------------------------------------- _pack_shard

BASES = np.frombuffer(b"ACGT", np.uint8)


def _backbone(rng, n):
    return (BASES[rng.integers(0, 4, n)].tobytes(),
            bytes(rng.integers(33, 60, n).astype(np.uint8)))


def columnar_items(seed, depths, first_index=0):
    """``[(result index, _Work)]`` over ONE synthetic store: window ``i``
    owns ``depths[i]`` consecutive rows of 20-64 lanes."""
    rng = np.random.default_rng(seed)
    lengths = rng.integers(20, LQ + 1, int(sum(depths)))
    store = make_store(seed, lengths, tail_row=len(lengths) - 1)
    items, r0 = [], 0
    for i, d in enumerate(depths):
        win = Window(i, 0, WindowType.TGS, *_backbone(rng, 48))
        win.attach_layers(store, r0, r0 + d)
        items.append((first_index + i, poa._Work(win, 200, {})))
        r0 += d
    return items


def hand_built_items(seed, depths, first_index):
    rng = np.random.default_rng(seed)
    items = []
    for i, d in enumerate(depths):
        win = Window(i, 0, WindowType.TGS, *_backbone(rng, 48))
        for li in range(d):
            seq, qual = _backbone(rng, int(rng.integers(20, LQ + 1)))
            win.add_layer(seq, qual if li % 2 else None, 0, 47)
        items.append((first_index + i, poa._Work(win, 200, {})))
    return items


def stage_b_overrides(seed, items, Lb):
    """Fetched stage-A state for every second window, as
    ``_finish_group_impl`` collects it."""
    rng = np.random.default_rng(seed)
    out = {}
    for ri, w in items[::2]:
        out[ri] = (rng.integers(0, 5, Lb).astype(np.uint8),
                   int(rng.integers(30, Lb)),
                   rng.integers(0, 9, Lb).astype(np.int32),
                   bool(ri % 4 == 0),
                   rng.integers(0, 20, w.n_layers).astype(np.int32),
                   rng.integers(20, 47, w.n_layers).astype(np.int32))
    return out


def shard_case(name):
    if name == "columnar":
        return columnar_items(21, [3, 5, 2, 4]), None
    if name == "two_stores":
        return (columnar_items(22, [3, 4])
                + columnar_items(23, [2, 5], first_index=2)), None
    if name == "columnar_and_hand_built":
        cols = columnar_items(24, [4, 3])
        # interleaved, so the two kinds' destination rows alternate
        hand = hand_built_items(25, [3, 2], first_index=2)
        return [cols[0], hand[0], cols[1], hand[1]], None
    assert name == "stage_b_repack"
    items = columnar_items(26, [3, 5, 2, 4, 6])
    return items, stage_b_overrides(27, items, Lb=64)


SHARD_CASES = ["columnar", "two_stores", "columnar_and_hand_built",
               "stage_b_repack"]


@pytest.fixture
def engine():
    return poa.TpuPoaConsensus(3, -5, -4, band=64, rounds=2)


def oracle_pack(monkeypatch, engine, *args):
    """``_pack_shard`` as it was: the index-matrix gather, assigned into
    the block through a fancy index."""
    def gather(store, rows, Lq, out, dest):
        out[dest] = oracle_gather(store, rows, Lq)
        return out
    with monkeypatch.context() as m:
        m.setattr(LayerStore, "gather_qpw", gather)
        return engine._pack_shard(*args)


@pytest.mark.parametrize("case", SHARD_CASES)
def test_pack_shard_arrays_equal_the_oracles(case, copier, engine,
                                             monkeypatch):
    items, overrides = shard_case(case)
    args = (items, LQ, 32, 8, 64, overrides)
    pair, win = engine._pack_shard(*args)
    pair0, win0 = oracle_pack(monkeypatch, engine, *args)
    assert len(pair) == 6 and len(win) == 5
    for got, want in zip((*pair, *win), (*pair0, *win0)):
        assert got.dtype == want.dtype and got.shape == want.shape
        assert got.tobytes() == want.tobytes()
    n, qpw, _, real, _, _ = pair
    k = sum(w.n_layers for _, w in items)
    assert real.sum() == k and qpw[:k].any() and not qpw[k:].any()
    # every real row holds its layer's lanes and zeros behind them
    assert all(row[:ln].all() and not row[ln:].any()
               for row, ln in zip(qpw[:k], n[:k]))


@pytest.mark.parametrize("case", SHARD_CASES)
def test_launch_puts_the_shards_arrays_as_they_were_written(
        case, engine, monkeypatch):
    """One shard: no concatenate between the pack and the put. What is
    on the device is the oracle's pack, byte for byte, and the counters
    say which rows the native copier wrote."""
    items, overrides = shard_case(case)
    rows0 = metrics.counter("consensus.lane_rows")
    copied0 = metrics.counter("consensus.lane_rows_copied")
    launch = engine._launch_group_impl(items, LQ, 64, overrides)
    columnar = sum(w.n_layers for _, w in items if w.store is not None)
    assert metrics.counter("consensus.lane_rows") - rows0 == columnar > 0
    assert metrics.counter("consensus.lane_rows_copied") - copied0 \
        == columnar
    B, nWp = launch["B"], launch["nWp"]
    pair0, win0 = oracle_pack(monkeypatch, engine, items, LQ, B, nWp,
                              64, overrides)
    n, qpw, win_of, real = (np.asarray(a) for a in launch["static"])
    bg, ed, bcodes, bweights, blen, covs, ever = (
        np.asarray(a) for a in launch["state"][:7])
    for got, want in zip((n, qpw, win_of, real, bg, ed, bcodes, bweights,
                          blen, covs, ever), (*pair0, *win0)):
        assert got.dtype == want.dtype
        assert got.tobytes() == want.tobytes()


def test_without_the_native_core_no_row_counts_as_copied(engine,
                                                         monkeypatch):
    monkeypatch.setattr(native, "available", lambda: False)
    items, _ = shard_case("columnar")
    rows0 = metrics.counter("consensus.lane_rows")
    copied0 = metrics.counter("consensus.lane_rows_copied")
    engine._pack_shard(items, LQ, 32, 8, 64)
    assert metrics.counter("consensus.lane_rows") - rows0 == 14
    assert metrics.counter("consensus.lane_rows_copied") == copied0


# --------------------------------------------- a whole job on ONE device

class _Stdout:
    """The CLI writes its FASTA to ``sys.stdout.buffer``."""

    def __enter__(self):
        self.raw = io.BytesIO()
        self.saved = sys.stdout
        sys.stdout = io.TextIOWrapper(self.raw, write_through=True)
        return self

    def __exit__(self, *exc):
        sys.stdout.flush()
        self.bytes = self.raw.getvalue()
        sys.stdout.detach()
        sys.stdout = self.saved


@pytest.fixture(scope="module")
def jobs(tmp_path_factory):
    """The same inputs through ``cli.main`` twice in this process: held
    to one device (``_ConsensusStream``: the cells' path), and on
    tier-1's eight virtual devices (the mesh path's padded packer).
    Steered here, in the test, not through an option of the program."""
    from racon_tpu.core import backends
    sys.path.insert(0, "tools")
    try:
        from simulate import write_inputs
    finally:
        sys.path.remove("tools")
    td = tmp_path_factory.mktemp("lane_jobs")
    paths = write_inputs(0.008, str(td), seed=35)
    argv = ["-t", "2", "-c", "1", "--tpualigner-batches", "1"]
    inputs = [paths["reads"], paths["overlaps"], paths["draft"]]
    out = {}
    auto_mesh = backends._auto_mesh
    run_padded = poa.TpuPoaConsensus._run_padded
    padded_runs = []

    def counted(self, *args, **kwargs):
        padded_runs.append(1)
        return run_padded(self, *args, **kwargs)
    try:
        trace.deactivate()
        poa.TpuPoaConsensus._run_padded = counted
        for tag, steer in (("one_device", lambda mesh: mesh),
                           ("mesh", auto_mesh)):
            backends._auto_mesh = steer
            del padded_runs[:]
            rep = td / f"{tag}.report.json"
            with _Stdout() as captured:
                rc = cli.main([*argv, "--run-report", str(rep), *inputs])
            assert rc == 0, tag
            out[tag] = {"fasta": captured.bytes,
                        "padded_runs": len(padded_runs),
                        "report": json.loads(rep.read_bytes())}
    finally:
        backends._auto_mesh = auto_mesh
        poa.TpuPoaConsensus._run_padded = run_padded
        trace.deactivate()
    return out


def test_one_device_job_equals_the_padded_paths_fasta(jobs):
    assert jobs["one_device"]["fasta"].startswith(b">")
    assert jobs["one_device"]["fasta"] == jobs["mesh"]["fasta"]
    # the two jobs did take different packers
    assert jobs["one_device"]["padded_runs"] == 0
    assert jobs["mesh"]["padded_runs"] >= 1


@pytest.mark.parametrize("tag", ["one_device", "mesh"])
def test_every_lane_row_of_a_job_is_copied_natively(jobs, tag):
    m = jobs[tag]["report"]["metrics"]
    rows = m["counters"]["consensus.lane_rows"]
    assert rows > 0
    assert m["counters"]["consensus.lane_rows_copied"] == rows
    assert m["counters"]["consensus.groups"] >= 1


@pytest.mark.parametrize("tag", ["one_device", "mesh"])
def test_lanes_is_a_leaf_of_pack_and_takes_no_idle_of_its_own(jobs, tag):
    """``poa.lanes`` times the block's construction inside ``poa.pack``;
    the device idle under it stays ``idle.poa.pack`` (the metric
    ``idle_consensus_feed_s`` lists that timer, not a new one)."""
    timers = jobs[tag]["report"]["metrics"]["timers"]
    assert 0 < timers["poa.lanes"] <= timers["poa.pack"]
    assert timers["poa.lanes"] + timers["poa.put"] \
        <= timers["poa.pack"] * 1.01 + 1e-4
    assert "idle.poa.lanes" not in timers
    dt = jobs[tag]["report"]["device_time"]
    assert "poa.lanes" not in dt["idle_by"]
    assert sum(dt["idle_by"].values()) == pytest.approx(dt["idle_s"],
                                                        abs=1e-4)
