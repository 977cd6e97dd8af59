#!/bin/bash
# The reasoning cell on the chip, run after run in one call:
# scripts/phi4flash_cell_chip.sh <tag> <trace> <seed> [...]
# scripts/exaone_cell_chip.sh with this cell's name: the same outputs under
# chiprun_out/<tag>/, the same DIR= (the parent under this PR's benchmark
# files has to fail at once) and TRAFFIC= (a sizing experiment).
export WORKLOAD=phi-4-mini-flash.batch-reasoning
exec bash "$(dirname "$0")/exaone_cell_chip.sh" "$@"
