"""Plain reference of ``racon --rounds N``: N one-shot runs chained
through files.

Run k+1's target file is run k's standard output as written; the reads
and every flag are unchanged (``-u`` or its absence applies in every
round). ``--rounds N`` must print, byte for byte, what the last run of
this chain prints. Independent of every line the loop adds
(``cli.main``'s loop, ``core/readset.py``, the held seed table): each
run here is the program without the option.
"""

from __future__ import annotations

import contextlib
import io
import os
import tempfile
from typing import List, Tuple


def chained_rounds(flags: List[str], reads: str, overlaps: str, draft: str,
                   rounds: int) -> Tuple[int, List[bytes]]:
    """``(exit code, [the FASTA each run printed])``; the chain stops
    at the first run that fails, as a pipeline would."""
    from .. import cli
    printed: List[bytes] = []
    with tempfile.TemporaryDirectory(prefix="racon-rounds-") as tmp:
        for k in range(rounds):
            out = io.TextIOWrapper(io.BytesIO(), write_through=True)
            with contextlib.redirect_stdout(out):
                rc = cli.main([*flags, reads, overlaps, draft])
            if rc != 0:
                return rc, printed
            printed.append(out.buffer.getvalue())
            draft = os.path.join(tmp, f"round_{k + 1}.fasta")
            with open(draft, "wb") as fh:
                fh.write(printed[-1])
    return 0, printed
