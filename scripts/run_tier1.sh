#!/usr/bin/env bash
# Tier-1 verify gate as the driver runs it (six xdist workers, files
# kept whole on one worker, pipefail, timeout, DOTS_PASSED count). Run
# from the repo root:
#
#   bash scripts/run_tier1.sh
#
# Exit code is pytest's; the DOTS_PASSED line is the pass count.
#
# One thing more than the driver's command: --module-budget=360. The run
# ends with "slowest test modules" (tests/conftest.py; it prints under
# xdist too), and a module outside tests/benchmarks/ over 360 s, a
# quarter of the cap, turns this run red at the PR that adds it. The
# driver's own command stays report-only. ALLOW_MULTIPLE_LIBTPU_LOAD=1 is
# the driver's too: the files of v5e compiles go to several workers, each
# loading the TPU library (no test takes a chip; never set it for a
# command that goes to the chip).
cd "$(dirname "$0")/.." || exit 1
set -o pipefail
rm -f /tmp/_t1.log
timeout -k 10 1470 env JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 \
  python -m pytest tests/ -q \
  -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
  -p xdist -n 6 --dist loadfile -p no:randomly --module-budget=360 \
  2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)
exit $rc
