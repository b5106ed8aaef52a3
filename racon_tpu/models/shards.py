"""Plain reference of ``racon --shards N`` (and of ``--max-ram``,
``--chips``, ``--workers``, ``wrapper.py --split``: every way into
``racon_tpu.exec``): the one-shot run.

The shard runner packs the targets into chunks, cuts each chunk's reads
and overlaps out of the job's files, polishes chunk by chunk and merges
the parts in target order; it must print, byte for byte, what the same
command prints without it. Independent of every line of ``exec/``
(index, planner, leases, part files, merge): the run here is the
program with the runner's options taken away.
"""

from __future__ import annotations

import contextlib
import io
from typing import List, Tuple

# the options that route a job into the shard runner (``cli.main``),
# by whether a value follows them
_VALUED = ("--shards", "--max-ram", "--shard-dir", "--workers", "--chips")
_BARE = ("--resume",)


def one_shot_argv(argv: List[str]) -> List[str]:
    """``argv`` without the shard runner's options."""
    out: List[str] = []
    skip = False
    for arg in argv:
        if skip:
            skip = False
        elif arg in _VALUED:
            skip = True
        elif arg not in _BARE and not arg.startswith(
                tuple(opt + "=" for opt in _VALUED)):
            out.append(arg)
    return out


def one_shot(argv: List[str]) -> Tuple[int, bytes]:
    """``(exit code, the FASTA printed)`` of the job ``argv`` describes,
    run one-shot: one ``Polisher`` over the whole input."""
    from .. import cli
    out = io.TextIOWrapper(io.BytesIO(), write_through=True)
    with contextlib.redirect_stdout(out):
        rc = cli.main(one_shot_argv(argv))
    return rc, out.buffer.getvalue()
