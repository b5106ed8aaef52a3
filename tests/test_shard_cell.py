"""``racon --shards 4`` over a fragmented draft, as the cell
``frag2m-shards4-paf30x`` runs it: held byte for byte to
``racon_tpu/models/shards.py`` (the one-shot run), with what the run
report says of the job (section ``shard_run``, span ``exec.commit``,
timers ``idle.exec.*``).

Whole jobs through ``cli.main`` with the JAX engines (``-c 1
--tpualigner-batches 1``), pinned to ONE device (tier-1's 8 virtual
devices would otherwise send them down the mesh path, which is not the
cell's), on the benchmark generator's ``tiny7c-30x``: the cell's seven
contigs at 12 kbp, which the planner packs one | two | two | two.
"""

import contextlib
import importlib.util
import io
import json
import pathlib

import pytest

from racon_tpu import cli, contracts, obs
from racon_tpu.core import backends, polisher
from racon_tpu.exec import manifest as mf
from racon_tpu.models import shards as reference
from racon_tpu.obs import device_time, metrics, report, trace
from racon_tpu.ops import poa

REPO = pathlib.Path(__file__).resolve().parents[1]
MS = 1_000_000

FLAGS = ["-t", "2", "-c", "1", "--tpualigner-batches", "1"]
FLAG_SETS = {"default": [], "unpolished": ["-u"]}
PLAN = [[0], [1, 6], [2, 5], [3, 4]]


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
    """Every job of this module, run once: per flag set the one-shot
    reference and ``--shards 4`` (with a report and a kept shard
    directory), and ``--shards 4`` with a compute fault injected into
    the second shard's first attempt, and ``--shards 4`` with the
    two-stage threshold under a shard's 256 pair rows (``two_stage``:
    the cell's shards pass it as they are). ``calls`` counts, per job, the
    consensus warm-ups asked for (``kicked``), the threads they started
    (``started``: the engine starts none for shapes it has warmed) and
    the waits for them (the threshold under which a job kicks none is
    lowered, so these tiny jobs kick like the cell's)."""
    td = tmp_path_factory.mktemp("shards")
    traffic = json.loads((REPO / "benchmark" / "traffic"
                          / "tiny7c-30x.json").read_bytes())
    paths = _simulate().write_inputs(traffic, 2**31 + 43, str(td))
    inputs = [paths["reads"], paths["overlaps"], paths["draft"]]
    out = {"paths": paths, "one": {}, "sharded": {}, "calls": {}}
    auto_mesh, backends._auto_mesh = backends._auto_mesh, lambda mesh: mesh
    min_pairs, polisher.WARMUP_MIN_PAIRS = polisher.WARMUP_MIN_PAIRS, 1
    kick, drain = (poa.TpuPoaConsensus.warmup_async,
                   poa.TpuPoaConsensus.drain_warmup)
    counts = {"kicked": 0, "started": 0, "drained": 0}

    def counted_kick(self, *a, **kw):
        counts["kicked"] += 1
        thread = kick(self, *a, **kw)
        counts["started"] += thread is not None
        return thread

    def counted_drain(self):
        counts["drained"] += 1
        return drain(self)

    poa.TpuPoaConsensus.warmup_async = counted_kick
    poa.TpuPoaConsensus.drain_warmup = counted_drain

    def job(tag, argv):
        counts.update(kicked=0, started=0, drained=0)
        rc, fasta = _run(argv)
        assert rc == 0, tag
        out["calls"][tag] = dict(counts)
        return fasta

    def sharded(tag, extra):
        rep, work = td / f"{tag}.report.json", td / f"{tag}.work"
        fasta = job(tag, [*FLAGS, *extra, "--shards", "4", "--shard-dir",
                          str(work), "--run-report", str(rep), *inputs])
        return {"fasta": fasta, "report": json.loads(rep.read_bytes()),
                "manifest": mf.load_manifest(str(work))}

    try:
        trace.deactivate()
        for tag, extra in FLAG_SETS.items():
            counts.update(kicked=0, started=0, drained=0)
            rc, fasta = reference.one_shot(
                [*FLAGS, *extra, "--shards", "4", "--shard-dir",
                 str(td / "never"), *inputs])
            assert rc == 0, tag
            out["one"][tag] = fasta
            out["calls"]["one." + tag] = dict(counts)
            out["sharded"][tag] = sharded(tag, extra)
        mp = pytest.MonkeyPatch()
        mp.setenv("RACON_TPU_FAULTS", "exec.polish:err@2")
        try:
            out["faulted"] = sharded("faulted", [])
        finally:
            mp.undo()
        mp.setattr(poa, "TWO_STAGE_MIN_PAIRS", 64)
        try:
            out["two_stage"] = sharded("two_stage", [])
        finally:
            mp.undo()
    finally:
        backends._auto_mesh = auto_mesh
        polisher.WARMUP_MIN_PAIRS = min_pairs
        poa.TpuPoaConsensus.warmup_async = kick
        poa.TpuPoaConsensus.drain_warmup = drain
        trace.deactivate()
        # the traced jobs' device watchers end with the module's jobs:
        # the next file of this worker counts its own
        device_time.stop_watchers()
    assert not (td / "never").exists()
    return out


# ------------------------------------------------------------- the bytes

def test_reference_takes_the_runners_options_away():
    argv = ["-t", "8", "--shards", "4", "-c", "1", "--max-ram=2G",
            "--resume", "--chips", "1", "--workers", "2", "--shard-dir",
            "d", "-u", "r.fastq", "o.paf", "d.fasta"]
    assert reference.one_shot_argv(argv) == [
        "-t", "8", "-c", "1", "-u", "r.fastq", "o.paf", "d.fasta"]


@pytest.mark.parametrize("tag", sorted(FLAG_SETS))
def test_shards_print_the_one_shot_runs_bytes(jobs, tag):
    fasta = jobs["sharded"][tag]["fasta"]
    assert fasta == jobs["one"][tag]
    # seven contigs, every one polished: -u changes nothing here, and
    # the merge put them back in the draft's order
    names = [line.split()[0] for line in fasta.split(b"\n")
             if line.startswith(b">")]
    assert names == [b">contig_%d" % i for i in range(7)]


def test_two_stage_shards_print_the_one_shot_runs_bytes(jobs):
    """Every shard's lone consensus group sent for stage A's rounds and
    its survivors repacked (or continued in place), as the cell's
    shards of 31,150 pairs are since PR 44: the one-shot run's bytes,
    which ran every group's whole budget in one dispatch."""
    fasta = jobs["two_stage"]["fasta"]
    assert fasta == jobs["one"]["default"]
    c = jobs["two_stage"]["report"]["metrics"]["counters"]
    # (a shard's contig-tail windows form a group of their own in the
    # half-width bucket, a few rows: one stage at any threshold)
    assert c["consensus.stage_a_groups"] == 4
    assert c["consensus.first_stage_groups"] >= 4
    assert (0 < c["consensus.stage_a_survivors"]
            < c["consensus.stage_a_windows"])
    # the default jobs' groups are under the threshold: one stage each
    c = jobs["sharded"]["default"]["report"]["metrics"]["counters"]
    assert c["consensus.first_stage_groups"] >= 4
    assert c["consensus.stage_a_groups"] == 0
    assert "consensus.stage_a_windows" not in c


@pytest.mark.parametrize("si", range(4))
def test_plan_packs_the_contigs_as_the_cells(jobs, si):
    """LPT over the planner's cost: the largest contig alone, the next
    three each with one of the small ones; four shards of nearly equal
    cost, as ``seven-contigs-30x``'s 500 | 400+100 | 350+150 | 300+200."""
    entries = jobs["sharded"]["default"]["manifest"]["shards"]
    assert len(entries) == 4
    assert entries[si]["contigs"] == PLAN[si]
    assert entries[si]["status"] == mf.DONE
    assert entries[si]["engine"] == "primary"


# ------------------------------------------------------------ the report

@pytest.mark.parametrize("key, want", [
    ("count", 4), ("primary", 4), ("retried", 0)])
def test_shard_run_counts(jobs, key, want):
    rep = jobs["sharded"]["default"]["report"]
    assert report.validate_report(rep) == []
    assert rep["schema_version"] == contracts.SCHEMA_VERSION >= 15
    assert set(rep["shard_run"]) == set(
        contracts.SECTION_KEYS["shard_run"])
    assert rep["shard_run"][key] == want
    assert len(rep["shards"]) == 4      # the rows stay where they were


def test_shard_run_walls_bytes_and_boundaries(jobs):
    entry = jobs["sharded"]["default"]
    run, timers = entry["report"]["shard_run"], \
        entry["report"]["metrics"]["timers"]
    assert 0 < run["first_wall_s"] < timers["exec.shard"]
    assert 0 < run["last_wall_s"] < timers["exec.shard"]
    # the first shard compiles what the later ones run
    assert run["first_compiles"] >= run["last_compiles"] >= 0
    assert run["part_bytes"] == len(entry["fasta"]) == sum(
        e["bytes"] for e in entry["manifest"]["shards"])
    assert run["extract_bytes"] > run["part_bytes"]
    # three boundaries, each at least a commit and an extract long
    assert run["boundary_idle_s"] > 0
    assert run["boundary_idle_s"] == \
        entry["report"]["device_time"]["boundary_idle_s"]
    assert run["boundary_idle_s"] < entry["report"]["device_time"]["idle_s"]


def test_one_shot_report_has_an_empty_shard_run(jobs):
    obs.begin()         # a run boundary, as cli.main marks one
    rep = report.build_report("cli", wall_s=0.1)
    assert report.validate_report(rep) == []
    assert rep["shard_run"]["count"] == 0
    assert rep["device_time"]["boundary_idle_s"] == 0


def test_commit_is_a_span_of_every_shard(jobs):
    timers = jobs["sharded"]["default"]["report"]["metrics"]["timers"]
    assert "exec.commit" in contracts.SPANS
    assert "exec.commit" not in contracts.TIMER_ONLY_SPANS
    # two fsyncs of the part, a state file and a manifest per shard
    assert 0 < timers["exec.commit"] < timers["exec.shard"]
    for name in ("exec.index", "exec.plan", "exec.extract", "exec.merge"):
        assert timers[name] > 0, name


def test_obs_prints_the_shard_run(jobs):
    text = report.shards_table(jobs["faulted"]["report"])
    assert "shards done 4 (on the device engines at the first attempt 3, " \
           "retried 1)" in text
    assert "cpu-retry" in text and "exec.commit" in text
    assert len(text.splitlines()) == 3 + 4 + 1


# ---------------------------------------------------------- the warm-up

@pytest.mark.parametrize("tag, kicked", [
    ("default", 4), ("unpolished", 4), ("faulted", 3), ("one.default", 1)])
def test_warmup_is_drained_once_a_job(jobs, tag, kicked):
    """A shard hands its engines on, warm-up and all (``final`` off, the
    rounds' rule), and the slot waits once, when it has no shard left:
    however many polishers, one drain — what the one-shot run does with
    its one. Every shard on the device engines asks for its warm-up, as
    a one-shot job does, and the engine starts a thread only for shapes
    it has not warmed: the first job of the module warms this traffic's
    (four equal shards derive one), every later one finds them."""
    calls = jobs["calls"][tag]
    assert (calls["kicked"], calls["drained"]) == (kicked, 1)
    assert calls["started"] <= 1
    assert sum(c["started"] for c in jobs["calls"].values()) >= 1


class _Warmable:
    """A consensus engine as the runner sees one: its warm-up's state."""

    def __init__(self, log, name):
        self.log, self.name = log, name

    def drain_warmup(self):
        self.log.append(self.name)


@pytest.mark.parametrize("shards, chips, ends", [
    # (shards, slots, how the shard claimed last by some slot ends)
    (4, 1, "polished"), (3, 2, "polished"), (3, 2, "no-overlaps"),
    (2, 2, "cpu-retry")])
def test_every_slot_drains_once_however_its_last_shard_ends(
        monkeypatch, tmp_path, shards, chips, ends):
    """Every polisher a shard builds is told ``final=False``, and each
    slot that leaves the drain loop waits for its engines' warm-ups
    once: with two slots and three shards (one slot polishes one shard
    and finds the other two taken), where a slot's last shard has no
    overlaps and builds no polisher, and where it went down the ladder
    to the CPU engines."""
    from racon_tpu.exec import runner as runner_mod
    finals, drained = [], []

    class FakePolisher:
        timings = {}

        def run(self, drop_unpolished):
            return []

    def spy(*a, **kw):
        finals.append(kw["final"])
        return FakePolisher()

    monkeypatch.setattr(runner_mod, "create_polisher", spy)
    r = runner_mod.ShardRunner("r.fastq", "o.paf", "d.fasta",
                               aligner_backend="tpu",
                               consensus_backend="tpu", n_shards=shards,
                               work_dir=str(tmp_path / "work"),
                               chips=chips)
    r.plan = runner_mod.ShardPlan(shards=[[i] for i in range(shards)],
                                  costs=[1] * shards, mode="shards")
    r.index = type("I", (), {"window_type": None})()
    slots = r._chip_slots()
    assert len(slots) == chips
    for w in slots:
        w.engines = (object(), _Warmable(drained, w.worker))
        w.cpu_engines = (object(), object())
    last = shards - 1

    def run_shard(si, shard, entry, manifest, beat, claim, worker,
                  use_mesh):
        cpu = ends == "cpu-retry" and si == last
        paths = {"n_overlaps": 0 if ends == "no-overlaps" and si == last
                 else 1, "reads": "r", "overlaps": "o", "targets": "t"}
        if paths["n_overlaps"]:
            r._polish_shard(paths, cpu=cpu, worker=worker)
        entry["status"] = mf.DONE

    monkeypatch.setattr(r, "_run_shard", run_shard)
    monkeypatch.setattr(r, "_unpolished_records", lambda paths: [])
    monkeypatch.setattr(r, "_note_terminal", lambda *a: None)
    manifest = {"shards": [{"id": si, "status": mf.PENDING}
                           for si in range(shards)]}
    (tmp_path / "work").mkdir()
    beat = type("B", (), {"update": lambda self, **kw: None,
                          "emit": lambda self, msg: None})()
    r._drain(manifest, beat)
    assert all(e["status"] == mf.DONE for e in manifest["shards"])
    assert finals == [False] * (shards - (ends == "no-overlaps"))
    assert sorted(drained) == sorted(w.worker for w in slots)


def test_a_warmup_kicked_beside_another_ends_after_it():
    """A shard with another geometry kicks its warm-up while the one
    before may still compile; the new thread goes behind the old, so the
    slot's one wait covers both."""
    import threading
    eng = poa.TpuPoaConsensus.__new__(poa.TpuPoaConsensus)
    gate, order = threading.Event(), []

    def slow():
        gate.wait(10)
        order.append("first")

    eng._warmup = threading.Thread(target=slow, daemon=True)
    eng._warmup.start()
    eng.mesh, eng._warmed_shapes = None, set()
    eng._warmup_shapes = lambda *a: [("shape",)]
    eng._pinned = lambda: (_ for _ in ()).throw(RuntimeError("no device"))
    thread = eng.warmup_async(500, 100, 10)
    assert thread is not None and thread is eng._warmup
    thread.join(0.1)
    assert thread.is_alive()        # behind the first
    gate.set()
    eng.drain_warmup()
    order.append("drained")
    assert order == ["first", "drained"]
    # the stand-in for a device refused the second warm-up, which says
    # so where a job's report would look: not this module's to leave
    metrics.clear("swallowed.")


# ------------------------------------------------------------ the ladder

def _records(fasta: bytes) -> list:
    lines = fasta.split(b"\n")
    return [b"\n".join(lines[i:i + 2]) for i in range(0, len(lines) - 1, 2)]


@pytest.mark.parametrize("key, want", [
    ("count", 4), ("primary", 3), ("retried", 1)])
def test_a_shard_down_the_ladder_is_counted(jobs, key, want):
    """A compute fault in one shard's first attempt: the shard is retried
    on the CPU engines and the job exits 0 — with the host path's
    consensus for that shard's contigs (the two paths never promised
    each other's bytes) and the one-shot run's for every other. Nothing
    else in the job says so: the report has to."""
    entry = jobs["faulted"]
    assert entry["report"]["shard_run"][key] == want
    rows = entry["report"]["shards"]
    assert sorted(r["engine"] for r in rows) == ["cpu-retry"] + ["primary"] * 3
    retried = next(r["id"] for r in rows if r["engine"] == "cpu-retry")
    theirs, ours = (_records(jobs["one"]["default"]),
                    _records(entry["fasta"]))
    assert len(theirs) == len(ours) == 7
    for ci in range(7):
        if ci not in PLAN[retried]:
            assert ours[ci] == theirs[ci], ci
    assert any(ours[ci] != theirs[ci] for ci in PLAN[retried])
    counters = entry["report"]["metrics"]["counters"]
    assert counters["exec.shards_primary"] == 3
    assert counters["exec.shards_done"] == 4


# ----------------------------------------------- idle under the runner

def _two_shard_job():
    """A slot thread (``main``) drives two shards; each shard's pipeline
    feeds the aligner from a thread of its own, born inside
    ``exec.shard``, and the consensus from ``main``. Fake clock, ms."""
    spans = {
        "main": [("exec.index", 0, 100 * MS), ("exec.plan", 100 * MS,
                                               110 * MS),
                 ("exec.shard", 120 * MS, 500 * MS),
                 ("exec.extract", 130 * MS, 150 * MS),
                 ("consensus", 300 * MS, 450 * MS),
                 ("stitch", 450 * MS, 460 * MS),
                 ("exec.commit", 460 * MS, 490 * MS),
                 ("exec.shard", 510 * MS, 900 * MS),
                 ("exec.extract", 520 * MS, 540 * MS),
                 ("consensus", 700 * MS, 850 * MS),
                 ("stitch", 850 * MS, 860 * MS),
                 ("exec.commit", 860 * MS, 890 * MS),
                 ("exec.merge", 910 * MS, 950 * MS)],
        "feed-0": [("parse.reads", 160 * MS, 200 * MS),
                   ("align", 200 * MS, 300 * MS)],
        "feed-1": [("parse.reads", 550 * MS, 600 * MS),
                   ("align", 600 * MS, 700 * MS)],
    }
    rows = [("0", "exec", "nw", "feed-0", 200 * MS, 300 * MS),
            ("0", "exec", "poa", "main", 300 * MS, 440 * MS),
            ("0", "exec", "nw", "feed-1", 600 * MS, 700 * MS),
            ("0", "exec", "poa", "main", 700 * MS, 840 * MS)]
    return device_time.account(rows, spans, 0, 1000 * MS, "main")


@pytest.mark.parametrize("span, seconds", [
    # the head: feed-0 holds no span until 160 ms; under it the slot
    # thread indexed, planned, opened the shard and extracted
    ("exec.index", 0.1), ("exec.plan", 0.01),
    # both extracts, and both commits (the second in the tail)
    ("exec.extract", 0.04), ("exec.commit", 0.06),
    ("exec.merge", 0.04),
    # exec.shard's own: 120-130 and 150-160 of the head, 490-500,
    # 510-520 and 540-550 of the boundary, 890-900 of the tail
    ("exec.shard", 0.06),
    ("parse.reads", 0.09), ("consensus", 0.02), ("stitch", 0.02),
    # between the plan and the first shard, between the shards, around
    # the merge: the slot thread held no span either
    ("unattributed", 0.08)])
def test_idle_under_the_runner_is_charged_to_its_spans(span, seconds):
    out = _two_shard_job()
    assert out["busy_s"] == pytest.approx(0.48)
    assert out["idle_s"] == pytest.approx(0.52)
    assert out["idle_by"][span] == pytest.approx(seconds)
    assert sum(out["idle_by"].values()) == pytest.approx(out["idle_s"])


def test_boundary_idle_is_last_interval_to_first_interval():
    """Shard 0's last device interval ends at 440 ms, shard 1's first
    begins at 600 ms: one boundary of 160 ms — the stitch, the commit,
    the extract, the parse — whatever the spans charge it to."""
    out = _two_shard_job()
    assert out["boundary_idle_s"] == pytest.approx(0.16)
    assert out["head_idle_s"] == pytest.approx(0.2)
    assert out["tail_idle_s"] == pytest.approx(0.16)


def test_a_one_shot_job_is_charged_as_before():
    """No span of the runner on any feeding thread: what a thread holds
    in no span stays ``unattributed``."""
    spans = {"main": [("consensus", 300 * MS, 450 * MS)],
             "feed-0": [("parse.reads", 160 * MS, 200 * MS)]}
    rows = [("0", "exec", "nw", "feed-0", 200 * MS, 300 * MS),
            ("0", "exec", "poa", "main", 300 * MS, 440 * MS)]
    out = device_time.account(rows, spans, 0, 500 * MS, "main")
    assert out["idle_by"] == {
        "parse.reads": pytest.approx(0.04), "consensus": pytest.approx(0.01),
        "unattributed": pytest.approx(0.21)}
    assert out["boundary_idle_s"] == 0


@pytest.mark.parametrize("tag", ["default", "faulted"])
def test_idle_families_of_a_real_job_sum_to_the_idle(jobs, tag):
    entry = jobs["faulted"] if tag == "faulted" else jobs["sharded"][tag]
    rep = entry["report"]
    timers = rep["metrics"]["timers"]
    idle = {k: v for k, v in timers.items() if k.startswith("idle.")}
    assert sum(idle.values()) == pytest.approx(
        rep["device_time"]["idle_s"], abs=1e-4)
    for name in ("exec.index", "exec.extract", "exec.commit",
                 "exec.merge", "exec.shard"):
        assert "idle." + name in idle, name
    # every second of a sharded job lies inside a span of the runner or
    # of the pipeline: next to nothing is left unattributed
    assert idle["idle.unattributed"] < 0.05 * rep["wall_s"]
