"""``ont-contig-auto`` against its plain reference and through the CLI.

- rows: the device path (``ops/overlap_seed.py`` + ``ops/chain.py``: the
  device join, the ragged chain stream) against
  ``racon_tpu/models/overlap.py`` on the benchmark generator's own
  reads — the same rows, spans included, tolerance 0 (every stage is
  integer arithmetic);
- recall against the generator's exact PAF on the same inputs;
- the option ``--overlaps {file,auto}``: one way to decide the mode;
- whole jobs pinned to ONE device (tier-1's 8 virtual devices would
  otherwise send ``cli.main`` down the mesh path, which is not the
  cell's): ``--overlaps auto`` equals the positional ``auto`` byte for
  byte, a second job compiles nothing, the distance to the truth is
  held to the host path's on the exact PAF, and the occupancy ledger
  knows the overlapper's programs.
"""

import functools
import importlib.util
import io
import json
import pathlib
import sys

import numpy as np
import pytest

from racon_tpu import cli, native
from racon_tpu.io import parsers
from racon_tpu.models import overlap as reference
from racon_tpu.obs import report, trace
from racon_tpu.ops import chain

REPO = pathlib.Path(__file__).resolve().parents[1]

# the benchmark's traffic (benchmark/traffic/one2m-30x.json) at a size a
# CPU test can afford: read and draft error as the cell's, 30x
TRAFFIC = {
    "contig_sizes": [20000], "coverage": 30, "read_len_mean": 7000,
    "read_len_sd": 1500, "read_len_min": 2000, "read_len_max": 8000,
    "read_len_draw": "quantiles",
    "read_error": {"del": 0.03, "ins": 0.03, "sub": 0.06},
    "draft_error": {"del": 0.02, "ins": 0.02, "sub": 0.06},
    "quality_char": "9", "overlaps": "paf",
}
SEEDS = {2**31 + 341: 20000, 342: 30000, 343: 40000}   # seed -> contig bp
# the share of reads the overlapper must find where the generator put
# them. Read on these three seeds: 85 of 85, 128 of 128, 171 of 171;
# the floor leaves room for one 2 kb read in a hundred whose seeds thin
# out under 12 % error (PERF.md section 6, PR 34)
RECALL_FLOOR = 0.97


@functools.lru_cache(maxsize=None)
def _simulate():
    spec = importlib.util.spec_from_file_location(
        "bench_simulate", REPO / "benchmark" / "harness" / "simulate.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def generated(seed: int):
    """``(reads, draft, paf rows)`` of one seed: read byte strings in
    file order, the draft contig, and per read ``(strand, t_begin,
    t_end)`` from the generator's exact PAF."""
    traffic = {**TRAFFIC, "contig_sizes": [SEEDS[seed]]}
    fastq, paf, fasta, _ = _simulate().simulate(traffic, seed)
    reads = fastq.split(b"\n")[1::4]
    draft = b"".join(fasta.split(b"\n")[1:])
    truth = []
    for line in paf.splitlines():
        col = line.split(b"\t")
        truth.append((int(col[4] == b"-"), int(col[7]), int(col[8])))
    assert len(truth) == len(reads)
    return reads, draft, truth


@functools.lru_cache(maxsize=None)
def device_rows(seed: int):
    reads, draft, _ = generated(seed)
    self_t = np.full(len(reads), -1, np.int64)
    return chain.find_overlaps(reads, [draft], self_t, k=15, w=5,
                               max_occ=64, min_seeds=4,
                               device_join=True, ragged=True)


@pytest.mark.parametrize("seed", sorted(SEEDS))
def test_device_rows_equal_the_plain_reference(seed):
    """Device join + ragged chain stream against the definitions: the
    same (query, target, strand) set, every span, seed count and score
    equal. Tolerance 0 bases: hashing, the join and the chain DP are
    integer arithmetic on both sides."""
    reads, draft, _ = generated(seed)
    got = device_rows(seed)
    want = reference.find_overlaps_np(
        reads, [draft], np.full(len(reads), -1, np.int64))
    assert want["q_ord"].size >= 0.9 * len(reads)
    for key in reference.ROW_KEYS:
        assert np.array_equal(got[key], want[key]), key
    # the stream's per-group emission is the same rows again
    parts = list(chain.iter_overlap_groups(
        reads, [draft], np.full(len(reads), -1, np.int64), k=15, w=5,
        max_occ=64, min_seeds=4, device_join=True))
    for key in reference.ROW_KEYS:
        assert np.array_equal(np.concatenate([p[key] for p in parts]),
                              want[key]), key


@pytest.mark.parametrize("seed", sorted(SEEDS))
def test_recall_against_the_generators_exact_paf(seed):
    """Share of reads with an overlap row on the right strand whose
    target span overlaps the one the generator cut the read from."""
    reads, _, truth = generated(seed)
    rows = device_rows(seed)
    found = np.zeros(len(reads), bool)
    for q, strand, tb, te in zip(rows["q_ord"], rows["strand"],
                                 rows["t_begin"], rows["t_end"]):
        want_strand, want_tb, want_te = truth[int(q)]
        if int(strand) == want_strand and tb < want_te and want_tb < te:
            found[int(q)] = True
    assert found.mean() >= RECALL_FLOOR, (int(found.sum()), len(reads))


# ------------------------------------------------------------ the option

POLISH_FLAGS = ["-t", "2", "-w", "500", "-q", "10", "-e", "0.3", "-m", "3",
                "-x", "-5", "-g", "-4"]


def _captured_overlaps(monkeypatch, argv):
    """What the program's entry points are handed as the overlaps
    argument for ``argv`` (nothing is polished)."""
    seen = {}

    def create_polisher(sequences, overlaps, targets, *a, **kw):
        seen["one-shot"] = overlaps
        raise ValueError("stop")

    class Runner:
        def __init__(self, sequences, overlaps, targets, **kw):
            seen["shards"] = overlaps
            raise ValueError("stop")

    def submit_and_stream(sock, spec, out, **kw):
        seen["submit"] = spec["overlaps"]
        return 0

    from racon_tpu import exec as exec_mod
    from racon_tpu.serve import client
    monkeypatch.setattr(cli, "create_polisher", create_polisher)
    monkeypatch.setattr(exec_mod, "ShardRunner", Runner)
    monkeypatch.setattr(client, "submit_and_stream", submit_and_stream)
    cli.main(argv)
    (value,) = seen.values()
    return value


@pytest.mark.parametrize("extra,positional,want", [
    ([], "ovl.paf", "ovl.paf"),
    (["--overlaps", "file"], "ovl.paf", "ovl.paf"),
    (["--overlaps", "file"], "auto", "auto"),
    ([], "auto", "auto"),
    (["--overlaps", "auto"], "ovl.paf", "auto"),
    (["--overlaps", "auto", "-f"], "ovl.paf", "auto"),
    (["--overlaps", "auto", "--shards", "2"], "ovl.paf", "auto"),
    (["--overlaps", "auto", "--submit", "/nonexistent.sock"], "ovl.paf",
     "auto"),
])
def test_overlaps_option_decides_the_mode_once(monkeypatch, extra,
                                               positional, want):
    """``--overlaps file`` and no option follow the positional;
    ``--overlaps auto`` puts the sentinel in the file's place at the
    CLI's edge, so one-shot, ``-f``, a shard run and ``--submit`` all
    see what the positional ``auto`` shows them."""
    got = _captured_overlaps(
        monkeypatch, [*extra, "reads.fastq", positional, "draft.fasta"])
    if want == "auto":
        assert parsers.is_auto_overlaps(got)
        assert parsers.overlaps_mode(got) == "auto"
    else:
        assert got.endswith(want)
        assert parsers.overlaps_mode(got) == "paf"


def test_overlaps_option_refuses_a_bad_value(capsys):
    with pytest.raises(SystemExit) as exc:
        cli.main(["--overlaps", "minimap2", "reads.fastq", "ovl.paf",
                  "draft.fasta"])
    assert exc.value.code == 2
    assert "--overlaps" in capsys.readouterr().err


def test_the_environment_no_longer_decides_the_mode(monkeypatch):
    """``RACON_TPU_OVERLAP`` is gone: setting it changes nothing."""
    from racon_tpu import flags
    monkeypatch.setenv("RACON_TPU_OVERLAP", "auto")
    assert "RACON_TPU_OVERLAP" not in flags.REGISTRY
    assert parsers.overlaps_mode("ovl.paf") == "paf"
    assert "--overlaps" in cli.build_parser().format_help()


# ------------------------------------------- whole jobs on ONE device

FEED_BATCH = 8


def _overlap_key(o):
    return (o.q_id, o.t_id, bool(o.strand), o.q_begin, o.q_end,
            o.t_begin, o.t_end)


class _Stdout:
    """The CLI writes its FASTA to ``sys.stdout.buffer``."""

    def __init__(self):
        self.raw = io.BytesIO()

    def __enter__(self):
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
    """Four jobs on one set of generated inputs in THIS process, pinned
    to one device: ``--overlaps auto`` (with a report), the positional
    ``auto``, ``--overlaps auto`` again (the warm job, with a report),
    and the host path on the generator's exact PAF."""
    from racon_tpu.core import backends, polisher
    td = tmp_path_factory.mktemp("auto_jobs")
    sim = _simulate()
    paths = sim.write_inputs({**TRAFFIC, "contig_sizes": [12000]},
                             2**31 + 34, str(td))
    truth = b"".join(open(paths["truth"], "rb").read().split(b"\n")[1:])
    device = ["-c", "1", "--tpualigner-batches", "1"]
    # one device: the single-device streams both cells run. Steered
    # here, in the test, not through an option of the program
    auto_mesh, backends._auto_mesh = backends._auto_mesh, lambda mesh: mesh
    # the streamed hand-off's batches as the align session is fed them,
    # cut every FEED_BATCH kept overlaps (the program's 512 would make
    # this job's 50 overlaps one batch)
    align_feed = polisher.Polisher._align_feed
    feed_batch = polisher.STREAM_FEED_OVERLAPS
    fed = []

    def spy(self, feed, *rest):
        fed.append([])

        def tee():
            for batch in feed:
                fed[-1].append([_overlap_key(o) for o in batch])
                yield batch

        return align_feed(self, tee(), *rest)

    polisher.Polisher._align_feed = spy
    polisher.STREAM_FEED_OVERLAPS = FEED_BATCH
    out = {"truth": truth, "paths": paths, "fed": fed}
    try:
        trace.deactivate()
        for tag, argv in (
                ("option", [*device, "--overlaps", "auto", paths["reads"],
                            paths["overlaps"], paths["draft"]]),
                ("positional", [*device, paths["reads"], "auto",
                                paths["draft"]]),
                ("again", [*device, "--overlaps", "auto", paths["reads"],
                           paths["overlaps"], paths["draft"]]),
                ("host", [paths["reads"], paths["overlaps"],
                          paths["draft"]])):
            rep = td / f"{tag}.report.json"
            with _Stdout() as captured:
                rc = cli.main([*POLISH_FLAGS, "--run-report", str(rep),
                               *argv])
            assert rc == 0, tag
            out[tag] = {"fasta": captured.bytes,
                        "report": json.loads(rep.read_bytes())}
    finally:
        backends._auto_mesh = auto_mesh
        polisher.Polisher._align_feed = align_feed
        polisher.STREAM_FEED_OVERLAPS = feed_batch
        trace.deactivate()
    return out


def _contig(fasta: bytes) -> bytes:
    return b"".join(fasta.split(b"\n")[1:])


def test_option_and_positional_auto_are_the_same_job(jobs):
    assert jobs["option"]["fasta"].startswith(b">")
    assert jobs["option"]["fasta"] == jobs["positional"]["fasta"]
    assert jobs["again"]["fasta"] == jobs["option"]["fasta"]
    for tag in ("option", "positional", "again"):
        rep = jobs[tag]["report"]
        assert report.validate_report(rep) == []
        assert rep["overlap"]["mode"] == "auto"
        # the file named in the overlaps position was never opened
        assert "parse.overlaps" not in rep["metrics"]["timers"]
        assert rep["metrics"]["counters"]["align.chunks"] > 0
    assert jobs["host"]["report"]["overlap"]["mode"] == "paf"


def test_align_session_is_fed_the_barrier_paths_batches(jobs):
    """What reaches ``sess.feed`` in the streamed job: the barrier
    path's rows (``find_overlaps(ragged=False)``) through
    ``_filter_overlaps``, in that order, cut where the hand-off cuts —
    after a whole query's rows, once ``FEED_BATCH`` kept overlaps of
    the queries before it are collected. Same pairs, same order, same
    boundaries, in every auto job."""
    import types

    from racon_tpu.core.overlap import Overlap
    from racon_tpu.core.polisher import Polisher, PolisherType
    reads = open(jobs["paths"]["reads"], "rb").read().split(b"\n")[1::4]
    draft = b"".join(
        open(jobs["paths"]["draft"], "rb").read().split(b"\n")[1:])
    rows = chain.find_overlaps(reads, [draft],
                               np.full(len(reads), -1, np.int64),
                               ragged=False)
    settings = types.SimpleNamespace(error_threshold=0.3,
                                     type=PolisherType.C)
    want, buf, run = [], [], []
    for i in range(rows["q_ord"].size):
        q = int(rows["q_ord"][i])
        o = Overlap.from_paf(
            b"", len(reads[q]), int(rows["q_begin"][i]),
            int(rows["q_end"][i]), "-" if rows["strand"][i] else "+",
            b"", len(draft), int(rows["t_begin"][i]),
            int(rows["t_end"][i]))
        o.q_id, o.t_id = 1 + q, 0      # the draft is sequence 0
        if run and run[-1].q_id != o.q_id:
            buf.extend(Polisher._filter_overlaps(settings, run))
            run = []
        run.append(o)
        last_of_query = (i + 1 == rows["q_ord"].size
                         or rows["q_ord"][i + 1] != q)
        if last_of_query and len(buf) >= FEED_BATCH:
            want.append([_overlap_key(o) for o in buf])
            buf = []
    buf.extend(Polisher._filter_overlaps(settings, run))
    want.append([_overlap_key(o) for o in buf])
    assert len(want) >= 4 and all(len(b) >= FEED_BATCH for b in want[:-1])
    # three auto jobs streamed; the host job on the PAF has no feed
    assert len(jobs["fed"]) == 3
    for fed in jobs["fed"]:
        assert fed == want
    counters = jobs["again"]["report"]["metrics"]["counters"]
    assert counters["overlap.intake_visits"] \
        == counters["overlap.chain_pairs"] > 0
    assert counters["overlap.chain_pairs"] \
        <= counters["overlap.candidate_pairs"]
    timers = jobs["again"]["report"]["metrics"]["timers"]
    for leaf in ("overlap.chain.plan", "overlap.emit", "overlap.rows"):
        assert timers[leaf] > 0, leaf
        assert "idle." + leaf not in timers
    assert timers["overlap.chain.plan"] + timers["overlap.emit"] \
        + timers["overlap.rows"] <= timers["align"]
    gauges = jobs["again"]["report"]["metrics"]["gauges"]
    assert 0 < gauges["overlap.first_emit_pairs"] \
        <= counters["overlap.chain_pairs"]


def test_second_auto_job_compiles_nothing(jobs):
    assert jobs["option"]["report"]["compiles"]["count"] > 0
    assert jobs["again"]["report"]["compiles"]["count"] == 0
    assert jobs["again"]["report"]["compiles"]["post_warm"] == 0


def test_auto_job_is_held_to_the_host_path_on_the_exact_paf(jobs):
    """The cell's own gate: distance to the truth no more than the host
    path's on the generator's exact overlaps plus 100 per contig."""
    truth = jobs["truth"]
    auto = native.edit_distance(_contig(jobs["option"]["fasta"]), truth)
    host = native.edit_distance(_contig(jobs["host"]["fasta"]), truth)
    assert auto <= host + 100, (auto, host)


def test_auto_job_counts_its_queries(jobs):
    c = jobs["again"]["report"]["metrics"]["counters"]
    assert c["overlap.queries"] > 0
    assert RECALL_FLOOR * c["overlap.queries"] \
        <= c["overlap.queries_kept"] <= c["overlap.queries"]
    assert c.get("overlap.join_bailouts", 0) == 0


@pytest.mark.parametrize("tag", ["option", "again"])
def test_ledger_knows_the_overlappers_programs(jobs, tag):
    """``device_time`` holds ``exec`` rows for the overlapper's programs
    and ``h2d`` rows for their puts, and the ``idle.*`` timers still sum
    to the ledger's idle seconds."""
    rep = jobs[tag]["report"]
    dt = rep["device_time"]
    by = dt["by_program"]
    for program in ("_minimizer_kernel", "_join_ramp_kernel",
                    "_join_expand_kernel", "_chain_kernel"):
        assert by[program]["count"] >= 1, program
    for put in ("overlap.seed.put", "overlap.join.put",
                "overlap.chain.put"):
        assert by[put]["count"] >= 1, put
    kinds = {(r[1], r[2]) for r in dt["timeline"]}
    assert ("exec", "_minimizer_kernel") in kinds
    assert ("h2d", "overlap.seed.put") in kinds
    assert dt["busy_s"] + dt["idle_s"] == pytest.approx(dt["window_s"],
                                                        abs=1e-5)
    assert sum(dt["idle_by"].values()) == pytest.approx(dt["idle_s"],
                                                        abs=1e-4)
    timers = rep["metrics"]["timers"]
    idle = {k[len("idle."):]: v for k, v in timers.items()
            if k.startswith("idle.")}
    assert idle == dt["idle_by"]
    assert any(name.startswith("overlap.") for name in idle)
    # every idle timer of an auto job is in some idle_* metric's list
    listed = set()
    for path in (REPO / "benchmark" / "metrics").glob("idle_*.json"):
        listed |= set(json.loads(path.read_bytes())["spans"])
    assert {"idle." + name for name in idle} <= listed
