#!/usr/bin/env bash
# Pre-commit entry point: the repo's static gates, fast enough to run on
# every commit (no tests, no accelerator — gates 1-2 are pure host-side
# analysis; gate 3 traces/compiles the registered jit programs on a pinned
# 2-device CPU platform, ~25 s, and never touches the TPU pool).
#
#   ./scripts/check.sh
#
# Gate 1: ba3clint — the repo-specific AST lint suite (rule catalog in
#         docs/static_analysis.md). Exit 1 on any unsuppressed finding.
# Gate 1b: ba3cflow — the interprocedural concurrency & lifecycle
#         analyzer (F1-F6, same doc): whole-repo call-graph analysis of
#         the actor/serving planes. Exit 1 on any unsuppressed finding.
# Gate 1c: ba3cwire — the wire-protocol & failure-path conformance
#         analyzer (W1-W6, same doc): codec-pair symmetry, header
#         versioning, receive-loop resilience, typed-reject accounting,
#         the metrics contract vs docs/observability.md, CRC coverage.
#         Then the stale-suppression audit for ALL THREE tools: a
#         disable= comment that masks nothing is itself a finding (S001).
# Gate 2: compileall — every shipped .py must at least byte-compile.
# Gate 3: ba3caudit — trace-level (jaxpr/HLO) invariants of the hot-path
#         entry points against the committed audit_manifest.json (same
#         doc). Exit 1 on any T-rule violation or manifest drift.
#
# CI runs exactly this script (.github/workflows/ci.yml `lint` job runs
# gates 1-2, the `flow` and `wire` jobs run gates 1b-1c with SARIF
# upload; the `audit` job runs gate 3), so a clean local run means clean
# CI static gates.
set -euo pipefail
cd "$(dirname "$0")/.."

echo "== ba3clint =="
python -m tools.ba3clint distributed_ba3c_tpu tools scripts train.py chip_smoke.py

echo "== ba3cflow =="
python -m tools.ba3cflow

echo "== ba3cwire =="
python -m tools.ba3cwire

echo "== suppression hygiene =="
python -m tools.ba3clint --check-suppressions distributed_ba3c_tpu tools scripts train.py chip_smoke.py
python -m tools.ba3cflow --check-suppressions
python -m tools.ba3cwire --check-suppressions

echo "== compileall =="
python -m compileall -q distributed_ba3c_tpu tools scripts tests train.py chip_smoke.py

if [[ "${BA3C_CHECK_NO_AUDIT:-0}" != 1 ]]; then
  echo "== ba3caudit =="
  python -m tools.ba3caudit
else
  # CI's lint job installs no jax; the dedicated `audit` job owns gate 3
  # there. Locally, never set this — the full pre-commit is all 3 gates.
  echo "== ba3caudit skipped (BA3C_CHECK_NO_AUDIT=1) =="
fi

echo "check.sh: all gates passed"
