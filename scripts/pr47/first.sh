#!/bin/bash
# the sparse layer's pieces alone, then the cell once, traced, with its controls
mkdir -p chiprun_out/pr47
PYTHONPATH=. python scripts/pr47/kernel_check.py > chiprun_out/pr47/kernel_check.txt 2>&1
tail -12 chiprun_out/pr47/kernel_check.txt
python benchmark/run.py --workload glm_5_3_flash.long_resident_sparse_decode --seed 2147483747 --seconds 30 --trace 1 --control 1 > chiprun_out/pr47/first_run.txt 2> chiprun_out/pr47/first_run.err
echo rc=$?
grep -v '"stage": "start"' chiprun_out/pr47/first_run.txt | cut -c1-3000 | tail -16
tail -5 chiprun_out/pr47/first_run.err | cut -c1-600
