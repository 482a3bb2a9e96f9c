#!/bin/bash
# The parent on the new cell (it has to exit non-zero within seconds, on its
# import), then the two cells whose decode program holds apex_mla_decode_fwd,
# parent against change in pairs (scripts/pairs.sh): their setup_s is what an
# edit near the kernel pays for (PR 44).
#   chiprun --timeout 3500 -- bash scripts/pr47/old_cells.sh <budget s> [pairs.sh's items]
root=$PWD; cell=glm_5_3_flash.long_resident_sparse_decode
over=$root/.chip_tree/parent_with_new_benchmark; mkdir -p $root/chiprun_out/pr47
rm -rf $over; cp -r $root/.chip_tree/parent $over
cp -r $root/.chip_tree/final/benchmark/. $over/benchmark/; cp $root/.chip_tree/final/BENCHMARK.json $over/
began=$(date +%s.%N)
(cd $over && timeout 300 python3 benchmark/run.py --workload $cell --seed 7 --seconds 30 --trace 0 > $root/chiprun_out/pr47/parent_try.out 2> $root/chiprun_out/pr47/parent_try.err)
echo "the parent on the new cell: rc=$? after $(python3 -c "import time; print(round(time.time() - $began, 1))") s: $(tail -1 $root/chiprun_out/pr47/parent_try.err | cut -c1-300)"
budget=$1; shift
[ $# -gt 0 ] || set -- w:deepseek_v3.resident_context_decode:11 deepseek_v3.resident_context_decode:2147483711 deepseek_v3.resident_context_decode:2147483712 w:ling3_flash_vl.many_stream_reasoning:11 ling3_flash_vl.many_stream_reasoning:2147483721 ling3_flash_vl.many_stream_reasoning:2147483722
bash scripts/pairs.sh $budget "$@"
