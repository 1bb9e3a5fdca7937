#!/usr/bin/env bash
# Tier-1 gate: the full test suite on CPU, importable with zero network
# access (optional deps like `hypothesis` are shimmed by tests/conftest.py,
# so a missing package must never break *collection*).
#
# The default collection includes the execution-plan layer's modules —
# tests/test_engine.py (planner: bucketing, cost model, --plan CLI),
# tests/test_trace_vec.py (vectorized trace synthesis parity) and
# tests/test_detectors.py (livelock/saturation monitors) — and this guard
# fails fast if any of them stops being collected.
set -euo pipefail
cd "$(dirname "$0")/.."
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
for mod in tests/test_engine.py tests/test_trace_vec.py tests/test_detectors.py tests/test_composed.py tests/test_workloads.py tests/test_zoo.py tests/test_bench.py tests/test_tpu_compile.py; do
  [[ -f "$mod" ]] || { echo "tier1: missing $mod" >&2; exit 1; }
done
# docs gates: public-surface docstrings and the generated CLI page
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python scripts/check_doc_coverage.py
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" python scripts/gen_cli_docs.py --check
PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" exec python -m pytest -x -q "$@"
