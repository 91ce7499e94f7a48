#!/usr/bin/env bash
# Canonical lint entry point (mirrors scripts/test.sh).
#
# apexlint itself is pure stdlib (it never imports JAX); the CPU pin only
# mirrors scripts/test.sh so no tool launched from here reaches for a chip.
#
# Usage: scripts/lint.sh [paths...] [--strict] [--json] [--write-baseline]
# No args = the [tool.apexlint] scope from pyproject.toml, strict mode
# (new findings AND stale baseline entries fail).
#
# Fast path: `scripts/lint.sh --changed-only` lints just the git-diff
# file set (worktree + index vs HEAD, plus untracked), strict, while the
# whole-program context still spans the full tree — the pre-commit loop.
set -euo pipefail
cd "$(dirname "$0")/.."
if [ "$#" -eq 0 ]; then
    set -- --strict
elif [ "$#" -eq 1 ] && [ "$1" = "--changed-only" ]; then
    set -- --strict --changed-only
fi
exec env JAX_PLATFORMS=cpu python -m apex_tpu.analysis "$@"
