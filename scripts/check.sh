#!/usr/bin/env bash
# One-step verification: the tier-1 test suite (speed is judged on the
# chip by bench/, not here).
#   scripts/check.sh            # everything
#   scripts/check.sh -m 'not slow'   # extra pytest args pass through
set -euo pipefail
cd "$(dirname "$0")/.."

# JAX persistent compilation cache: repeated check runs pay XLA
# compilation once per machine (thresholds dropped so every kernel persists)
export JAX_COMPILATION_CACHE_DIR="${JAX_COMPILATION_CACHE_DIR:-$PWD/artifacts/jax_cache}"
export JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS=0
export JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES=0
mkdir -p "$JAX_COMPILATION_CACHE_DIR"

# COVERAGE=1 adds a coverage run over the repro package (requires
# pytest-cov; CI installs it on the fast split and uploads coverage.xml)
if [[ "${COVERAGE:-0}" == "1" ]]; then
    if python -c 'import pytest_cov' 2>/dev/null; then
        python -m pytest -x -q --cov=repro --cov-report=xml "$@"
    else
        echo "COVERAGE=1 set but pytest-cov is not installed; running" \
             "without coverage" >&2
        python -m pytest -x -q "$@"
    fi
else
    python -m pytest -x -q "$@"
fi
