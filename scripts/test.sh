#!/usr/bin/env bash
# Canonical test entry point.  Tests always run on the 8-device virtual CPU
# mesh (tests/conftest.py pins it too); the chip is reached only through
# chip_smoke.py, one process per chip.
set -euo pipefail
cd "$(dirname "$0")/.."
exec env JAX_PLATFORMS=cpu python -m pytest tests/ "$@"
