#!/bin/bash
# The assistant cell on the chip, run after run in one call:
# scripts/falconh1_cell_chip.sh <tag> <trace> <seed> [...]
# scripts/falconh1_cell_chip.sh pairs <tag> <trace> <seed> [...]
# scripts/exaone_cell_chip.sh with this cell's name: the same outputs under
# chiprun_out/<tag>/, the same pairs (every seed on .parent/ and on the
# working tree), the same DIR= (the parent under this PR's benchmark files
# has to fail at once: "unknown model preset") and TRAFFIC= (a sizing
# experiment: a file OUTSIDE chiprun_out/). WORKLOAD=<cell> runs another
# cell.
export WORKLOAD=${WORKLOAD:-falcon-h1-34b.batch-assistant}
exec bash "$(dirname "$0")/exaone_cell_chip.sh" "$@"
