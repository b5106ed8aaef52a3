"""The reads of a job that polishes in rounds (``racon --rounds N``).

One polisher is one round: it parses its targets and its reads, uses
them up (``Sequence.transmute`` frees the name of every read and the
forward bytes and quality of a read its round used on the reverse
strand) and ends. The rounds of one job map the SAME reads to a draft
that changes, so the reads need an owner that outlives a round:

- parsed once, by the first round that asks (:meth:`ReadSet.sequences`,
  under the ``parse.reads`` span, where a one-shot polisher parses);
- a round that is not the job's last may only add to a read (its
  reverse complement, cached on the ``Sequence``), never take from it:
  the polisher keeps names, bytes and qualities while ``final`` is off;
- the read-side seed table of the overlapper is built by the first
  round's join into :attr:`ReadSet.seed_tables` and taken from there by
  every later one (``racon_tpu.ops.chain._read_table``).
"""

from __future__ import annotations

from typing import Dict, List, Optional

from .. import obs
from ..io import parsers
from ..obs import metrics
from .sequence import Sequence


class ReadSet:
    def __init__(self, path: str):
        self.path = path
        self._sequences: Optional[List[Sequence]] = None
        # (k, w) -> the overlapper's table of these reads
        self.seed_tables: Dict[tuple, tuple] = {}

    def sequences(self) -> List[Sequence]:
        """The reads in file order, parsed on the first call."""
        if self._sequences is None:
            with obs.span("parse.reads"):
                parse = parsers.sequence_parser_for(self.path)
                self._sequences = [Sequence(r.name, r.data, r.quality)
                                   for r in parse(self.path)]
            metrics.inc("rounds.reads_parsed", len(self._sequences))
        return self._sequences
