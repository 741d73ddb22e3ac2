#!/usr/bin/env bash
# Tier-1 verify gate as the driver runs it (six xdist workers, files
# kept whole on one worker, pipefail, timeout, DOTS_PASSED count). Run
# from the repo root:
#
#   bash scripts/run_tier1.sh
#
# Exit code is pytest's; the DOTS_PASSED line is the pass count.
cd "$(dirname "$0")/.." || exit 1
set -o pipefail
rm -f /tmp/_t1.log
timeout -k 10 1470 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
  -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
  -p xdist -n 6 --dist loadfile -p no:randomly 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)
exit $rc
