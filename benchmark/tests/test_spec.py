"""Every cell of ``BENCHMARK.json`` resolves to its data files, and the
file keeps to the contract's names, units and limits."""

import json
import os

import pytest

from bench_paths import BENCH, ROOT, TINY_BENCHMARK

from harness import readers, simulate, spec

END_TO_END_KEYS = {"name", "unit", "better", "bound", "source"}
PER_LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmark"]
    assert bench["command"] == ["python3", "benchmark/run.py"]
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10
    assert 1 <= len(bench["workloads"]) <= 24
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)


def test_every_cell_resolves_and_its_traffic_is_servable(bench):
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        simulate.check_traffic(cell.traffic)
        assert cell.chips in (1, 4)
        assert "-c" in cell.job_flags()
        assert "-c" not in cell.reference_flags()
        assert cell.per_layer and len(cell.end_to_end) >= 2
        for _, mfile in cell.per_layer:
            assert mfile["reader"] in readers.KINDS


def test_names_units_and_texts_use_only_the_allowed_characters(bench):
    names = []
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmark/")
        assert spec.PATH_RE.match(c["file"])
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert len(c["reduced"]) <= 16
        names += [c["name"], *c["reduced"]]
        for text in (c["source"], c["why"]):
            assert 1 <= len(text) <= 200 and "\n" not in text \
                and "\t" not in text
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        names += [w["name"], w["config"], w["traffic"]]
        assert 1 <= len(w["why"]) <= 200
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == END_TO_END_KEYS
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == PER_LAYER_KEYS
        assert m["source"] in spec.SOURCES
        assert 1 <= len(m["layer"]) <= 200
    for m in bench["end_to_end"] + bench["per_layer"]:
        names.append(m["name"])
        assert spec.UNIT_RE.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for name in names:
        assert spec.NAME_RE.match(name), name
    metric_names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    assert "setup_s" in metric_names
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert all(m["moves"] in e2e for m in bench["per_layer"])


def test_files_under_paths_are_named_from_a_names_characters():
    for folder, _, files in os.walk(BENCH):
        if "__pycache__" in folder or os.sep + "cache" in folder:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(folder, f), ROOT)
            assert spec.PATH_RE.match(rel), rel


def test_a_new_cell_comes_as_data_only():
    """The tests' tiny cells are a stand-in BENCHMARK.json, two config
    files and two traffic files: no line of the harness names them."""
    cell = spec.load_cell("tiny", TINY_BENCHMARK)
    assert cell.genome_bases == 20000 and cell.chips == 1
    four = spec.load_cell("tiny-chips4", TINY_BENCHMARK)
    assert four.job_flags()[-2:] == ["--chips", "4"]
    with open(TINY_BENCHMARK) as fh:
        tiny = json.load(fh)
    assert [m["name"] for m in tiny["per_layer"]] == \
        [m["name"] for m in spec.load_benchmark()["per_layer"]]


def test_a_broken_spec_is_an_error_not_a_default(tmp_path):
    with pytest.raises(spec.SpecError, match="no workload"):
        spec.load_cell("no-such-cell")
    with open(TINY_BENCHMARK) as fh:
        tiny = json.load(fh)
    tiny["workloads"][0]["traffic"] = "one2m-30x"   # 2 Mbp under a 0.02 Mbp config
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(tiny))
    with pytest.raises(spec.SpecError, match="bases"):
        spec.load_cell("tiny", str(path))
    tiny["workloads"][0]["traffic"] = "tiny20k-30x"
    tiny["per_layer"][0]["unit"] = "ms"
    path.write_text(json.dumps(tiny))
    with pytest.raises(spec.SpecError, match="unit"):
        spec.load_cell("tiny", str(path))
