#!/usr/bin/env bash
# Lint gate: bbtpu-lint (project AST rules BB001-BB006 + README
# env-table drift, scripts/analyze.sh) then ruff over the package,
# tests and entry scripts. Ruff config lives in pyproject.toml
# ([tool.ruff]); run with --fix to apply safe autofixes (e.g. deleting
# unused imports) in place.
set -euo pipefail
cd "$(dirname "$0")/.."

scripts/analyze.sh

if ! command -v ruff >/dev/null 2>&1 && ! python -m ruff --version >/dev/null 2>&1; then
    echo "lint: ruff not installed; skipping (pip install ruff to enable)" >&2
    exit 0
fi

RUFF=ruff
command -v ruff >/dev/null 2>&1 || RUFF="python -m ruff"

exec $RUFF check "$@" bloombee_tpu tests __graft_entry__.py
