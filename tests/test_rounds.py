"""``racon --overlaps auto --rounds N``: N polishing rounds in one
process, held byte for byte to ``racon_tpu/models/rounds.py`` (N
one-shot runs chained through files).

Whole jobs through ``cli.main``, pinned to ONE device (tier-1's 8
virtual devices would otherwise send them down the mesh path, which is
not the cells'), on the benchmark generator's traffic at a size a CPU
test can afford: one 6 kb contig at 30x of 2-6 kb reads on both
strands, 12 % read error against a 10 % draft, and a second draft
contig no read maps to — the one ``-u`` keeps and its absence drops,
so the two flag sets hand different target sets to round 2.
"""

import contextlib
import importlib.util
import io
import json
import pathlib
import re

import numpy as np
import pytest

from racon_tpu import cli
from racon_tpu.core import backends
from racon_tpu.io import parsers
from racon_tpu.models import rounds as reference
from racon_tpu.obs import report, trace

REPO = pathlib.Path(__file__).resolve().parents[1]

FLAGS = ["-t", "2", "-c", "1", "--tpualigner-batches", "1",
         "--overlaps", "auto"]
FLAG_SETS = {"default": [], "unpolished": ["-u"]}
MOST_ROUNDS = 3
HEADER = re.compile(rb"^>contig_\d+ LN:i:\d+ RC:i:\d+ XC:f:\d\.\d{6}$")


def _simulate():
    spec = importlib.util.spec_from_file_location(
        "bench_simulate", REPO / "benchmark" / "harness" / "simulate.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(argv):
    """``(exit code, what the job printed)``."""
    out = io.TextIOWrapper(io.BytesIO(), write_through=True)
    with contextlib.redirect_stdout(out):
        rc = cli.main(argv)
    return rc, out.buffer.getvalue()


@pytest.fixture(scope="module")
def jobs(tmp_path_factory):
    """Every job of this module, run once: per flag set the plain
    reference's chain of three one-shot runs, ``--rounds 2`` and
    ``--rounds 3`` (each with a report and a span trace); and
    ``--rounds 1`` beside them. ``reads_after_first`` is the job's read
    set as round 1 of a ``--rounds 3`` job left it."""
    td = tmp_path_factory.mktemp("rounds")
    traffic = json.loads((REPO / "benchmark" / "traffic"
                          / "tiny20k-30x.json").read_bytes())
    paths = _simulate().write_inputs(
        {**traffic, "contig_sizes": [6000]}, 2**31 + 41, str(td))
    rng = np.random.default_rng(41)
    with open(paths["draft"], "ab") as fh:
        fh.write(b">contig_1\n"
                 + np.frombuffer(b"ACGT", np.uint8)[
                     rng.integers(0, 4, 2500)].tobytes() + b"\n")
    inputs = [paths["reads"], paths["overlaps"], paths["draft"]]
    out = {"paths": paths, "chain": {}, "loop": {}}
    # one device: the single-device streams the cells run. Steered
    # here, in the test, not through an option of the program
    auto_mesh, backends._auto_mesh = backends._auto_mesh, lambda mesh: mesh
    round_end = cli._round_end

    def spy(k, mark, polisher):
        if k == 1 and polisher._reads is not None:
            out.setdefault("reads_after_first", [
                (s.name, s.data, s.quality, s._reverse_complement)
                for s in polisher._reads.sequences()])
        return round_end(k, mark, polisher)

    cli._round_end = spy
    try:
        trace.deactivate()
        for tag, extra in FLAG_SETS.items():
            rc, printed = reference.chained_rounds(
                [*FLAGS, *extra], *inputs, MOST_ROUNDS)
            assert rc == 0 and len(printed) == MOST_ROUNDS, tag
            out["chain"][tag] = printed
            for n in (MOST_ROUNDS, 2):
                rep = td / f"{tag}.{n}.report.json"
                spans = td / f"{tag}.{n}.trace.json"
                rc, fasta = _run([*FLAGS, *extra, "--rounds", str(n),
                                  "--run-report", str(rep),
                                  "--trace", str(spans), *inputs])
                assert rc == 0, (tag, n)
                out["loop"][tag, n] = {
                    "fasta": fasta,
                    "report": json.loads(rep.read_bytes()),
                    "spans": json.loads(spans.read_bytes())}
        rep = td / "one.report.json"
        rc, fasta = _run([*FLAGS, "--rounds", "1", "--run-report",
                          str(rep), *inputs])
        assert rc == 0
        out["one"] = {"fasta": fasta,
                      "report": json.loads(rep.read_bytes())}
    finally:
        backends._auto_mesh = auto_mesh
        cli._round_end = round_end
        trace.deactivate()
    return out


@pytest.mark.parametrize("n", [2, 3])
@pytest.mark.parametrize("tag", sorted(FLAG_SETS))
def test_rounds_equal_the_chained_one_shot_runs(jobs, tag, n):
    """THE semantics: ``--rounds N`` prints what the N-th of N one-shot
    runs chained through files prints."""
    got = jobs["loop"][tag, n]["fasta"]
    assert got.startswith(b">contig_0 ")
    assert got == jobs["chain"][tag][n - 1]
    # the rounds differ, so the equality above compares something
    assert jobs["chain"][tag][n - 1] != jobs["chain"][tag][n - 2]


def test_unpolished_contig_is_kept_by_u_and_dropped_without_it(jobs):
    """``-u`` or its absence is applied in EVERY round: the contig no
    read maps to reaches the last round's FASTA only with ``-u``, and
    then as it came in."""
    draft = open(jobs["paths"]["draft"], "rb").read().split(b"\n")
    for n in (2, 3):
        assert b">contig_1" not in jobs["loop"]["default", n]["fasta"]
        lines = jobs["loop"]["unpolished", n]["fasta"].split(b"\n")
        assert lines[2].startswith(b">contig_1 ")
        assert lines[2].endswith(b"XC:f:0.000000")
        assert lines[3] == draft[3]


@pytest.mark.parametrize("n", [2, 3])
@pytest.mark.parametrize("tag", sorted(FLAG_SETS))
def test_header_tags_are_single_after_every_round(jobs, tag, n):
    """Round k+1 names its targets as a parser would: up to the first
    blank, so the LN / RC / XC tags of round k are gone."""
    headers = [ln for ln in jobs["loop"][tag, n]["fasta"].split(b"\n")
               if ln.startswith(b">")]
    assert headers and all(HEADER.match(h) for h in headers), headers


def test_round_one_leaves_every_read_whole(jobs):
    """``Sequence.transmute`` frees a read's name, and the forward
    bytes and quality of a read its round used on the reverse strand;
    round 2 maps the same reads again. After round 1 of a job with a
    round behind it every read still is what the parser made of it,
    and the reverse complements round 1 made are there to be reused."""
    want = [(r.name, r.data, r.quality)
            for r in parsers.parse_fastq(jobs["paths"]["reads"])]
    kept = jobs["reads_after_first"]
    assert [k[:3] for k in kept] == want
    assert all(name and data and qual for name, data, qual, _ in kept)
    reverse = [rc is not None for *_, rc in kept]
    assert any(reverse) and not all(reverse)    # reads of both strands


@pytest.mark.parametrize("n", [2, 3])
def test_reads_are_parsed_and_seeded_once(jobs, n):
    job = jobs["loop"]["default", n]
    c = job["report"]["metrics"]["counters"]
    n_reads = len(jobs["reads_after_first"])
    assert c["rounds.reads_parsed"] == n_reads
    assert c["rounds.read_tables_built"] == 1
    assert c["rounds.read_tables_reused"] == n - 1
    assert c["rounds.completed"] == n and c["rounds.followups"] == n - 1
    # every round offered every read to the overlapper
    assert c["overlap.queries"] == n * n_reads
    names = [ev["name"] for ev in job["spans"]["traceEvents"]
             if ev.get("ph") == "X"]
    assert names.count("parse.reads") == 1
    assert names.count("parse.targets") == 1
    assert names.count("round") == n
    assert names.count("round.handoff") == n - 1
    assert names.count("overlap.seed") == n


@pytest.mark.parametrize("n", [2, 3])
def test_report_has_the_rounds_section(jobs, n):
    rep = jobs["loop"]["default", n]["report"]
    assert report.validate_report(rep) == []
    rounds = rep["rounds"]
    assert set(rounds) == {
        "count", "first_wall_s", "last_wall_s", "first_compiles",
        "last_compiles", "first_overlaps_kept", "last_overlaps_kept",
        "handoff_s", "rows"}
    assert rounds["count"] == n == len(rounds["rows"])
    assert [r["round"] for r in rounds["rows"]] == list(range(1, n + 1))
    assert rounds["first_wall_s"] == rounds["rows"][0]["wall_s"] > 0
    assert rounds["last_wall_s"] == rounds["rows"][-1]["wall_s"] > 0
    assert rounds["first_overlaps_kept"] > 0
    assert rounds["last_overlaps_kept"] > 0
    assert rounds["rows"][0]["handoff_s"] == 0
    assert all(r["handoff_s"] > 0 for r in rounds["rows"][1:])
    # the rounds and the hand-offs between them lie inside the job
    timers = rep["metrics"]["timers"]
    assert timers["round"] == pytest.approx(
        sum(r["wall_s"] for r in rounds["rows"]), abs=1e-3)
    assert timers["round"] + timers["round.handoff"] <= rep["wall_s"]
    assert len(report.rounds_table(rounds).splitlines()) == n + 1
    # every round's join was offered the one held read table and took
    # the entries that can match its own draft: the table's last line
    counters = rep["metrics"]["counters"]
    offered = counters["overlap.join_read_entries"]
    assert offered % n == 0
    assert 0 < counters["overlap.join_read_kept"] < offered
    assert report.rounds_table(rounds, counters).splitlines()[n + 1] \
        .startswith(f"join: {counters['overlap.join_read_kept']} of ")


def test_one_round_is_the_options_absence(jobs):
    """``--rounds 1`` takes the one-shot path: the bytes of a run
    without the option (the chain's first), one round in the report,
    no read table held or reused."""
    assert jobs["one"]["fasta"] == jobs["chain"]["default"][0]
    rep = jobs["one"]["report"]
    assert report.validate_report(rep) == []
    assert rep["rounds"]["count"] == 1
    c = rep["metrics"]["counters"]
    assert c["rounds.read_tables_built"] == 1
    assert "rounds.read_tables_reused" not in c
    assert "rounds.followups" not in c


@pytest.mark.parametrize("extra,positional,named", [
    ([], "ovl.paf", "overlaps from a file"),
    (["--overlaps", "file"], "ovl.paf", "overlaps from a file"),
    (["--overlaps", "auto", "-f"], "ovl.paf", "-f"),
    (["--overlaps", "auto", "--chips", "1"], "ovl.paf", "--chips"),
    (["--overlaps", "auto", "--shards", "2"], "ovl.paf", "--shards"),
    (["--overlaps", "auto", "--max-ram", "4G"], "ovl.paf", "--max-ram"),
    (["--overlaps", "auto", "--resume"], "ovl.paf", "--resume"),
    (["--overlaps", "auto", "--workers", "2"], "ovl.paf", "--workers"),
    (["--overlaps", "auto", "--submit", "/nonexistent.sock"], "ovl.paf",
     "--submit"),
])
def test_refused_combinations_exit_2_and_say_why(capsys, extra, positional,
                                                 named):
    with pytest.raises(SystemExit) as exc:
        cli.main([*extra, "--rounds", "2", "reads.fastq", positional,
                  "draft.fasta"])
    assert exc.value.code == 2
    err = capsys.readouterr().err
    assert "--rounds 2 cannot be combined with" in err and named in err


@pytest.mark.parametrize("argv,named", [
    (["--rounds", "2", "--serve", "/nonexistent.sock"], "--serve"),
    (["--rounds", "0", "reads.fastq", "auto", "draft.fasta"],
     "--rounds must be >= 1"),
])
def test_refused_without_a_job(capsys, argv, named):
    with pytest.raises(SystemExit) as exc:
        cli.main(argv)
    assert exc.value.code == 2
    assert named in capsys.readouterr().err


@pytest.mark.parametrize("name,value,named", [
    ("RACON_TPU_CHIPS", "1", "RACON_TPU_CHIPS"),
])
def test_refused_environment(monkeypatch, capsys, name, value, named):
    """The chip scheduler's environment switch is refused by name, not
    ignored."""
    monkeypatch.setenv(name, value)
    with pytest.raises(SystemExit) as exc:
        cli.main(["--rounds", "2", "reads.fastq", "auto", "draft.fasta"])
    assert exc.value.code == 2
    assert named in capsys.readouterr().err
