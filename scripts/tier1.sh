#!/usr/bin/env bash
# Tier-1 verification: the full test suite, the plan linter and the
# gateway chaos sweep.  One entry point for builders and CI; run from
# the repo root.
#
#   scripts/tier1.sh            # everything (slow model/serve suites too)
#   scripts/tier1.sh --quick    # deselect the multi-minute slow suites
#   scripts/tier1.sh -m 'not slow'   # extra pytest args pass through
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

if [[ "${1:-}" == "--quick" ]]; then
    shift
    set -- -m 'not slow' "$@"
fi

python -m pytest -x -q "$@"
python scripts/lint_plans.py                     # trace-time plan lint gate
python scripts/chaos.py --seeds 0 --requests 32  # gateway fault-tolerance gate
