#!/usr/bin/env bash
# bbtpu-lint gate: project-specific AST rules (BB001-BB013) plus the
# README env-switch-table and ARCHITECTURE lock-hierarchy-table drift
# checks, against the committed baseline.
#
#   scripts/analyze.sh                     # the CI gate
#   scripts/analyze.sh --update-baseline   # accept current findings
#   scripts/analyze.sh --fix-env-docs      # regenerate README table
#   scripts/analyze.sh --fix-lock-docs     # regenerate ARCHITECTURE table
#   scripts/analyze.sh --json              # machine-readable findings
#   scripts/analyze.sh --list-rules
set -euo pipefail
cd "$(dirname "$0")/.."

# --check-env-docs imports the package to populate the env registry;
# that needs no accelerator, so keep it on the CPU.
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

case "${1:-}" in
    --update-baseline|--fix-env-docs|--fix-lock-docs|--list-rules|--dump-env-table)
        exec python -m bloombee_tpu.analysis "$@"
        ;;
esac

exec python -m bloombee_tpu.analysis --check-env-docs --check-lock-docs "$@"
