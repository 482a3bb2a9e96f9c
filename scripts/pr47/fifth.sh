#!/bin/bash
# the new cell with its served tokens altered; the parent on the new cell and
# one pair of the DeepSeek cell; then further seeds of the new cell
TREE=.chip_tree/final OPTS="--option break_tokens=1" bash scripts/pr47/seeds.sh broken 700 2147483901
bash scripts/pr47/old_cells.sh 800 deepseek_v3.resident_context_decode:2147483711
TREE=.chip_tree/final bash scripts/pr47/seeds.sh setD ${1:-600} 2147483841 2147483842
