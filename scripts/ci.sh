#!/usr/bin/env bash
# Tier-1 verification — the exact command the roadmap pins:
#   PYTHONPATH=src python -m pytest -x -q
# Run from the repo root (locally or in CI). Extra args go to pytest.
#
# `scripts/ci.sh --bench [check_bench args...]` instead runs the perf gate:
# measure every artifact named by the gate manifest (benchmarks/gates.json)
# into a scratch dir with `benchmarks/run.py --only <slugs>`, then compare
# each gated metric against the committed baselines with
# `scripts/check_bench.py --manifest` (regression beyond a gate's tolerance
# fails the job).
#
# `scripts/ci.sh --lint-contracts` runs the AST contract lint over src/repro
# (retired kwargs, quantize flow, raw knob literals — see
# src/repro/analysis/astlint.py).
#
# `scripts/ci.sh --analysis [run_analysis args...]` runs the program-contract
# analysis lane: lint-contracts plus the registry checkers (retrace audit,
# dtype-flow lint, donation/aliasing verification) over 8 forced host
# devices, as the CI `analysis` job does.
#
# `scripts/ci.sh --faults [pytest args...]` runs the chaos lane: the
# fault-injection suite (tests/test_serve_faults.py — failure isolation,
# retry/breaker, deadlines, degradation, and the sharded {1,2,4} chaos
# parity test) over 8 forced host devices, as the CI `faults` job does.
set -euo pipefail
cd "$(dirname "$0")/.."

if [[ "${1:-}" == "--lint-contracts" ]]; then
  shift
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m repro.analysis.astlint src/repro "$@"
  exit 0
fi

if [[ "${1:-}" == "--analysis" ]]; then
  shift
  PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m repro.analysis.astlint src/repro
  XLA_FLAGS="--xla_force_host_platform_device_count=8${XLA_FLAGS:+ $XLA_FLAGS}" \
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python scripts/run_analysis.py "$@"
  exit 0
fi

if [[ "${1:-}" == "--faults" ]]; then
  shift
  XLA_FLAGS="--xla_force_host_platform_device_count=8${XLA_FLAGS:+ $XLA_FLAGS}" \
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m pytest -q tests/test_serve_faults.py "$@"
  exit 0
fi

if [[ "${1:-}" == "--bench" ]]; then
  shift
  out="$(mktemp -d)"
  trap 'rm -rf "$out"' EXIT
  slugs="$(python scripts/check_bench.py --manifest benchmarks/gates.json \
    --list-slugs)"
  # 8 forced host devices: search_perf's sharded scenario runs in-process
  # over the devices it sees
  XLA_FLAGS="--xla_force_host_platform_device_count=8${XLA_FLAGS:+ $XLA_FLAGS}" \
    BENCH_OUT_DIR="$out" PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python benchmarks/run.py --only "$slugs"
  python scripts/check_bench.py --manifest benchmarks/gates.json \
    --baseline-dir . --new-dir "$out" "$@"
  exit 0
fi

PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -x -q "$@"
