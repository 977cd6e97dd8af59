#!/bin/bash
# The long-document cell on the chip, run after run in one call:
# scripts/solar_cell_chip.sh <tag> <trace> <seed> [...]
# scripts/solar_cell_chip.sh pairs <tag> <trace> <seed> [...]
# scripts/exaone_cell_chip.sh with this cell's name: the same outputs under
# chiprun_out/<tag>/, the same pairs (every seed on .parent/ and on the
# working tree), the same DIR= (the parent under this PR's benchmark files
# has to fail at once) and TRAFFIC= (a sizing experiment).
export WORKLOAD=${WORKLOAD:-solar-open2-250b.batch-longdoc}
exec bash "$(dirname "$0")/exaone_cell_chip.sh" "$@"
