#!/bin/bash
# The agent-turns cell on the chip, run after run in one call:
# scripts/nemotronh_cell_chip.sh <tag> <trace> <seed> [...]
# scripts/exaone_cell_chip.sh with this cell's name: the same outputs under
# chiprun_out/<tag>/, the same DIR= (another checkout: the committed files,
# git archive $(git write-tree) | tar -x -C .proof; the parent under this
# PR's benchmark files, which has to fail at once: "unknown model preset")
# and TRAFFIC= (a sizing experiment: a file OUTSIDE chiprun_out/).
# WORKLOAD=<cell> runs another cell.
export WORKLOAD=${WORKLOAD:-nemotron-3-super-120b-a12b.batch-agentturns}
exec bash "$(dirname "$0")/exaone_cell_chip.sh" "$@"
