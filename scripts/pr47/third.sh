#!/bin/bash
# one call: the parent on the new cell and one pair of the DeepSeek cell; the
# new cell with its served tokens altered; then further seeds of the new cell
bash scripts/pr47/old_cells.sh 900 deepseek_v3.resident_context_decode:2147483711
TREE=.chip_tree/final OPTS="--option break_tokens=1" bash scripts/pr47/seeds.sh broken 700 2147483901
TREE=.chip_tree/final bash scripts/pr47/seeds.sh setB ${1:-1500} 2147483811 2147483812 2147483813 2147483814 2147483815 2147483816
