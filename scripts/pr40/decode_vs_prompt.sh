#!/bin/bash
# PR 40, review round, call 3: scripts/pr40/decode_vs_prompt.py at full size.
#   chiprun --timeout 1200 -- bash scripts/pr40/decode_vs_prompt.sh
mkdir -p chiprun_out/p40r
python3 scripts/pr40/decode_vs_prompt.py > chiprun_out/p40r/decode_vs_prompt.out 2> chiprun_out/p40r/decode_vs_prompt.err
echo "rc=$?"; tail -n 5 chiprun_out/p40r/decode_vs_prompt.err | cut -c1-400
python3 -c "
import json
d = json.load(open('chiprun_out/p40r/decode_vs_prompt.json'))
print(json.dumps(d))"
