"""The plain reference of a cell: the same job on the host path.

The configuration's ``reference_flags`` put every alignment through
``native/nw.cpp`` and every window through the spoa-faithful
``native/poa.cpp`` — no device kernel, no packer, no band ladder. It
runs in a child with ``JAX_PLATFORMS=cpu``, so it neither asks for the
chip nor adds to the measured process's memory, and after the window,
so it is no part of ``setup_s``. Its polished distance to the truth
(measured by the benchmark's own ``distance.py``) is kept as a small
JSON record under ``benchmark/cache/`` per (cell, seed): later runs of
that seed in that checkout read it instead of polishing again. The key
in the record covers the configuration, the traffic and the seed, so a
record never outlives what it was made from.
"""

from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys
import time

from . import distance
from .spec import BENCH_DIR, ROOT, Cell

CACHE_DIR = os.path.join(BENCH_DIR, "cache")


def record_path(cell: Cell, seed: int) -> str:
    return os.path.join(CACHE_DIR, f"reference.{cell.name}.{seed}.json")


def _key(cell: Cell, seed: int) -> str:
    blob = json.dumps([cell.config, cell.traffic, seed], sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def reference_record(cell: Cell, seed: int, inputs: dict,
                     work_dir: str) -> dict:
    """``{"key", "distance", "truth_bases", "wall_s", "cached"}`` of the
    host-path polish of ``inputs``; raises if the reference itself
    fails (a run cannot be judged without it)."""
    path, key = record_path(cell, seed), _key(cell, seed)
    try:
        with open(path, "r", encoding="utf-8") as fh:
            rec = json.load(fh)
        if rec.get("key") == key:
            return {**rec, "cached": True}
    except (OSError, ValueError):
        pass
    fasta = os.path.join(work_dir, "reference.fasta")
    argv = [sys.executable, "-m", "racon_tpu", *cell.reference_flags(),
            inputs["reads"], inputs["overlaps"], inputs["draft"]]
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    t0 = time.perf_counter()
    with open(fasta, "wb") as out:
        proc = subprocess.run(argv, cwd=ROOT, env=env, stdout=out,
                              stderr=subprocess.PIPE, check=False)
    wall = time.perf_counter() - t0
    if proc.returncode != 0:
        raise RuntimeError(
            f"the host-path reference exited {proc.returncode}: "
            f"{proc.stderr.decode(errors='replace')[-2000:]}")
    dist, truth_bases = distance.total_distance(fasta, inputs["truth"])
    rec = {"key": key, "distance": dist, "truth_bases": truth_bases,
           "wall_s": wall}
    os.makedirs(CACHE_DIR, exist_ok=True)
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "w", encoding="utf-8") as fh:
        json.dump(rec, fh)
    os.replace(tmp, path)
    return {**rec, "cached": False}
