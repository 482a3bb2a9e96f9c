#!/bin/bash
# with the routers' biases balanced: the cell once traced with its controls,
# then untraced seeds; all from the committed files (.chip_tree/final)
TREE=.chip_tree/final TRACE=1 OPTS="--control 1" bash scripts/pr47/seeds.sh traced 1300 2147483821
TREE=.chip_tree/final bash scripts/pr47/seeds.sh setC ${1:-1900} 2147483831 2147483832 2147483833 2147483834
