#!/usr/bin/env bash
# Style/compile gate (analog of ci/checks/style.sh).
set -e
cd "$(dirname "$0")/../.."
python -m compileall -q racon_tpu tests __graft_entry__.py
# no tabs in Python sources; 100-col hard ceiling
! grep -rn "$(printf '\t')" racon_tpu --include='*.py'
python - <<'PY'
import pathlib, sys
bad = [f"{p}:{i}" for p in pathlib.Path("racon_tpu").rglob("*.py")
       for i, line in enumerate(p.read_text().splitlines(), 1)
       if len(line) > 100]
if bad:
    print("lines over 100 columns:", *bad[:20], sep="\n  ")
    sys.exit(1)
PY
# ruff baseline (pyproject [tool.ruff]); advisory-skip when the tool is
# not in the image — graftlint (the tools/analysis shard) is the hard
# correctness gate either way
if command -v ruff >/dev/null 2>&1; then
    ruff check racon_tpu tools tests
else
    echo "style: ruff not installed, baseline skipped"
fi
echo "style: OK"
