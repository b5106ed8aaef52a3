"""Resolve a cell of ``BENCHMARK.json`` into the data files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by name:

* ``configs[].file``                      the configuration as it is run
* ``benchmark/traffic/<traffic>.json``    the simulator's parameters
* ``benchmark/metrics/<metric>.json``     the metric's reader and its
  parameters

so a later PR adds a cell, a configuration, a traffic mix or a metric by
adding files and entries, never by editing this harness.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH_RE = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")

CONFIG_KEYS = ("name", "source", "genome_mbp", "source_genome_mbp",
               "window_length", "residual_ppm_limit", "flags", "device_flags",
               "reference_flags", "assumed", "guarantees")


class SpecError(Exception):
    """``BENCHMARK.json`` or a file it names is missing or malformed."""


def _load(path: str) -> dict:
    try:
        with open(path, "r", encoding="utf-8") as fh:
            return json.load(fh)
    except (OSError, ValueError) as e:
        raise SpecError(f"{path}: {e}") from e


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # the configuration file
    traffic: dict           # the traffic file
    traffic_path: str
    end_to_end: list        # BENCHMARK.json entries this cell reports
    per_layer: list         # (BENCHMARK.json entry, metric file) pairs

    @property
    def genome_bases(self) -> int:
        return sum(self.traffic["contig_sizes"])

    def job_flags(self) -> list:
        """The flags of a job as a user of this configuration types it;
        a four-chip cell drives the chips through ``--chips``."""
        flags = [*self.config["assumed"]["threads"],
                 *self.config["device_flags"], *self.config["flags"]]
        if self.chips > 1:
            flags += ["--chips", str(self.chips)]
        return flags

    def reference_flags(self) -> list:
        """The same job on the host path (the plain reference)."""
        return [*self.config["assumed"]["threads"],
                *self.config["reference_flags"], *self.config["flags"]]


def load_benchmark(path: str = "") -> dict:
    """``BENCHMARK.json`` at the root, or the stand-in a test names; the
    files it names resolve against the root either way."""
    return _load(path or os.path.join(ROOT, "BENCHMARK.json"))


def _applies(entry: dict, cell_name: str) -> bool:
    return "workloads" not in entry or cell_name in entry["workloads"]


def metric_file(name: str) -> str:
    return os.path.join(BENCH_DIR, "metrics", name + ".json")


def traffic_file(name: str) -> str:
    return os.path.join(BENCH_DIR, "traffic", name + ".json")


def load_cell(name: str, bench_path: str = "") -> Cell:
    bench = load_benchmark(bench_path)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                        f"(it has {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {name!r} names config "
                        f"{w['config']!r}, which BENCHMARK.json lacks")
    config = _load(os.path.join(ROOT, configs[w["config"]]["file"]))
    missing = [k for k in CONFIG_KEYS if k not in config]
    if missing:
        raise SpecError(f"{configs[w['config']]['file']} lacks {missing}")
    tpath = traffic_file(w["traffic"])
    traffic = _load(tpath)
    if "contig_sizes" not in traffic:
        raise SpecError(f"{tpath} lacks contig_sizes")
    if abs(sum(traffic["contig_sizes"]) - config["genome_mbp"] * 1e6) > 0.5:
        raise SpecError(
            f"traffic {w['traffic']!r} holds "
            f"{sum(traffic['contig_sizes'])} bases, config "
            f"{config['name']!r} states {config['genome_mbp']} Mbp")
    per_layer = []
    for entry in bench["per_layer"]:
        if not _applies(entry, name):
            continue
        mfile = _load(metric_file(entry["name"]))
        for key in ("name", "unit", "better", "source", "layer", "moves"):
            if mfile.get(key) != entry[key]:
                raise SpecError(
                    f"metric {entry['name']!r}: {key} is "
                    f"{mfile.get(key)!r} in its file and {entry[key]!r} "
                    f"in BENCHMARK.json")
        per_layer.append((entry, mfile))
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, traffic_path=tpath,
                end_to_end=[e for e in bench["end_to_end"]
                            if _applies(e, name)],
                per_layer=per_layer)
