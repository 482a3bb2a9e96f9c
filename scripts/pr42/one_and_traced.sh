#!/bin/bash
# PR 42: the new cell once untraced with both controls, once traced (the
# working tree). Run twice: before and after the convolution tail's fix.
out=$PWD/chiprun_out/p42; mkdir -p $out
cell=ling3_flash_vl.many_stream_reasoning
python3 benchmark/run.py --workload $cell --seed 3000000029 --seconds 30 --trace 0 --control 1 > $out/run2.out 2> $out/run2.err
echo "run2 rc=$? $(tail -n 1 $out/run2.out | cut -c1-1500)"
grep -h '"stage": "\(built\|warm\|resident\|window\|correct\|control\)"' $out/run2.out | cut -c1-1800
tail -n 5 $out/run2.err | cut -c1-600
python3 benchmark/run.py --workload $cell --seed 2718281829 --seconds 30 --trace 1 --option list_kernels=1 > $out/traced2.out 2> $out/traced2.err
echo "traced2 rc=$? $(tail -n 1 $out/traced2.out | cut -c1-4000)"
grep -h '"stage": "\(resident\|window\|correct\|trace\)"' $out/traced2.out | cut -c1-3000
tail -n 5 $out/traced2.err | cut -c1-600
