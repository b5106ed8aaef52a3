#!/usr/bin/env python3
"""The benchmark's one command::

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

runs one cell of ``BENCHMARK.json`` once, on the machine it is started
on, and prints as the last line of its standard output one JSON object
with ``correct``, ``attempted``, ``failed``, ``metrics`` and ``device``
(and ``breakdown`` when traced). Before it, one line per number compared
beside its limit. See ``benchmark/README.md`` and
``benchmark/harness/cell.py``.

Exit code 0 means a result line was printed (``correct`` may be false).
No TPU, or fewer chips than the cell asks for: exit 3 and no result.
Anything else that stops the run: the traceback on stderr, exit 1, no
result.

``--rehearse`` is for the tests and for a dry run without the chip: the
platform check is recorded as failed instead of stopping the run, the
run ends ``correct: false``, and its readings go under
``rehearsal_readings``, never under ``metrics``.
``--benchmark-json`` names a stand-in for ``BENCHMARK.json`` (the tests'
tiny cell).
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--benchmark-json", default="")
    args = ap.parse_args(argv)

    from harness import cell as harness_cell
    from harness import spec
    try:
        cell = spec.load_cell(args.workload, args.benchmark_json)
        result = harness_cell.run_cell(
            cell, args.seed, args.seconds, args.trace, T0,
            require_tpu=not args.rehearse)
    except harness_cell.NoDevice as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return harness_cell.EXIT_NO_DEVICE
    except Exception:
        traceback.print_exc()
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
