#!/usr/bin/env bash
# Tier-1 test entry point.  Fails fast — and loudly — on collection
# errors so "suite can't import" is never mistaken for "suite passes".
#
#   scripts/test.sh                full tier-1 suite
#   scripts/test.sh --fast         skip the slow training-integration tier
#                                  (end-to-end Trainer runs; minutes on
#                                  CPU) and the multi-device tier (its
#                                  own CI job runs it per PR)
#   scripts/test.sh --multidevice  ONLY the multi-device tier: every
#                                  case subprocesses onto 8 fake host
#                                  devices (tests/_multidevice.py), so
#                                  this tier needs no special env
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

# Known-red ledger.  Every entry would be a test we KNOW fails and have
# chosen to ship anyway.  The list stays empty by rule — adding an entry
# fails the suite loudly instead of quietly normalizing red — which is
# not a claim that the suite is green: failing tests are reported in
# CHANGES.md until they are fixed.
KNOWN_RED=()
if [ "${#KNOWN_RED[@]}" -ne 0 ]; then
    echo "FATAL: known-red list must stay empty; fix or delete the tests" >&2
    printf '  known-red: %s\n' "${KNOWN_RED[@]}" >&2
    exit 3
fi

FAST=0
MULTIDEVICE=0
ARGS=()
for a in "$@"; do
    case "$a" in
        --fast) FAST=1 ;;
        --multidevice) MULTIDEVICE=1 ;;
        *) ARGS+=("$a") ;;
    esac
done

PYTEST_ARGS=(-x -q)
if [ "$MULTIDEVICE" -eq 1 ]; then
    PYTEST_ARGS+=(tests/test_distributed.py tests/test_sharded_serving.py)
elif [ "$FAST" -eq 1 ]; then
    PYTEST_ARGS+=(--ignore=tests/test_train_integration.py
                  --ignore=tests/test_distributed.py
                  --ignore=tests/test_sharded_serving.py)
fi

if ! python -m pytest -q --collect-only >collect.err 2>&1; then
    echo "FATAL: test collection failed" >&2
    cat collect.err >&2
    rm -f collect.err
    exit 2
fi
rm -f collect.err

exec python -m pytest "${PYTEST_ARGS[@]}" ${ARGS[@]+"${ARGS[@]}"}
