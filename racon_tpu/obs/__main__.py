"""``python -m racon_tpu.obs --check FILE`` — run-report validation
(the CI e2e check drives this); ``python -m racon_tpu.obs gaps
RUN_REPORT DEVICE_TRACE`` — the report's occupancy ledger laid on a
device trace (:mod:`racon_tpu.obs.gaps`); ``python -m racon_tpu.obs
compiles RUN_REPORT`` — the report's compiled programs, one line each,
and the set-up totals (:mod:`racon_tpu.obs.compilewatch`); ``python -m
racon_tpu.obs rounds RUN_REPORT`` — the rounds of a ``--rounds N`` job,
one line each (the report's ``rounds`` section); ``python -m
racon_tpu.obs shards RUN_REPORT`` — the shard runner's job: the
``shard_run`` section, a line per shard, the ``idle.exec.*`` timers."""

import sys

from .report import _main

sys.exit(_main(sys.argv[1:]))
