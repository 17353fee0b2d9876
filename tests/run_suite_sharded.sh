#!/usr/bin/env bash
# Run the test suite in several pytest PROCESSES instead of one.
#
# Why: on some hosts this round, XLA:CPU segfaults late in a single
# multi-hour pytest process (inside backend compilation, after hundreds
# of compiled executables accumulate; every test FILE passes in
# isolation, and the same suite ran 575- and 628-green in one process
# earlier on the same day — the crash is jaxlib/XLA process-lifetime
# state, not a test failure).
# Sharding bounds each process's lifetime while keeping full coverage.
#
# Usage: tests/run_suite_sharded.sh [num_shards]   (default 4)
set -u
cd "$(dirname "$0")/.."
n=${1:-4}
files=$(ls tests/test_*.py | sort)
total=$(echo "$files" | wc -l)
per=$(( (total + n - 1) / n ))
fail=0
i=0
for chunk in $(echo "$files" | xargs -n "$per" echo | tr ' ' ',' ); do
    i=$((i + 1))
    echo "=== shard $i/$n: $(echo "$chunk" | tr ',' ' ' | wc -w) files ==="
    # shellcheck disable=SC2086
    python -m pytest $(echo "$chunk" | tr ',' ' ') -q || fail=1
done
exit $fail
