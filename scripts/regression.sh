#!/bin/bash
# Nightly regression harness (parity:
# curvine-tests/regression/daily_regression_test.sh — drives the full
# suite + dryrun + the chip smoke and emits an HTML report + JSON summary).
#
# Usage: scripts/regression.sh <project_root> <result_dir> [pytest-expr]
# Exit code: 0 = everything green, 1 = any stage failed.

set -u

if [ $# -lt 2 ]; then
    echo "Usage: $0 <project_root> <result_dir> [pytest-expr]"
    echo "Example: $0 /root/repo /tmp/regression-\$(date +%F)"
    exit 1
fi

ROOT="$1"
OUT="$2"
EXPR="${3:-}"
mkdir -p "$OUT"
cd "$ROOT" || exit 1

STAMP=$(date -u +%FT%TZ)
FAIL=0

run_stage() {   # name, logfile, cmd...
    local name="$1" log="$2"; shift 2
    echo "=== $name ==="
    local t0=$SECONDS
    if "$@" > "$OUT/$log" 2>&1; then
        echo "$name: PASS ($((SECONDS - t0))s)"
        echo "{\"stage\": \"$name\", \"status\": \"pass\", \"secs\": $((SECONDS - t0))}" >> "$OUT/stages.jsonl"
    else
        echo "$name: FAIL ($((SECONDS - t0))s) — see $OUT/$log"
        echo "{\"stage\": \"$name\", \"status\": \"fail\", \"secs\": $((SECONDS - t0))}" >> "$OUT/stages.jsonl"
        FAIL=1
    fi
}

: > "$OUT/stages.jsonl"

if [ -n "$EXPR" ]; then
    run_stage pytest pytest.log python -m pytest tests/ -q -k "$EXPR"
else
    run_stage pytest pytest.log python -m pytest tests/ -q
fi
run_stage dryrun-multichip dryrun.log \
    python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"
# needs a TPU and fails without one
run_stage chip-smoke smoke.log python chip_smoke.py

# ---- HTML report ----
{
    echo "<!doctype html><meta charset=utf-8><title>curvine-tpu regression $STAMP</title>"
    echo "<style>body{font:14px system-ui;margin:2rem}table{border-collapse:collapse}"
    echo "td,th{border:1px solid #ccc;padding:4px 10px}.pass{color:#0a0}.fail{color:#c00}</style>"
    echo "<h1>curvine-tpu nightly regression</h1><p>$STAMP · $(git -C "$ROOT" rev-parse --short HEAD 2>/dev/null)</p>"
    echo "<table><tr><th>stage</th><th>status</th><th>secs</th></tr>"
    while read -r line; do
        s=$(echo "$line" | python -c "import json,sys; d=json.load(sys.stdin); print(d['stage'], d['status'], d['secs'])")
        set -- $s
        echo "<tr><td>$1</td><td class=$2>$2</td><td>$3</td></tr>"
    done < "$OUT/stages.jsonl"
    echo "</table>"
    echo "<p>logs: pytest.log · dryrun.log · smoke.log</p>"
} > "$OUT/report.html"

echo "report: $OUT/report.html"
exit $FAIL
