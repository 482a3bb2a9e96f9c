#!/bin/bash
# sha256 of the lowered text of every tiny configuration's serving programs
# (GPT, hybrid, Nemotron, DeepSeek; float32 and bfloat16 pools; prefill and
# decode): the parent commit (a `git archive` of it under /root/scratch/parent)
# against the working tree. `start=None`, `window=None` and `precision=None`
# have to lower to the parent's programs.   bash scripts/pr40/shacmp.sh
here=$(cd "$(dirname "$0")/../.." && pwd)
(cd /root/scratch/parent && PYTHONPATH=/root/scratch/parent JAX_PLATFORMS=cpu python $here/scripts/pr40/sha.py 2>&1 | grep -v "^E0\|^E1" > /root/scratch/sha_parent.txt)
(cd $here && PYTHONPATH=$here JAX_PLATFORMS=cpu python $here/scripts/pr40/sha.py 2>&1 | grep -v "^E0\|^E1" > /root/scratch/sha_mine.txt)
diff /root/scratch/sha_parent.txt /root/scratch/sha_mine.txt && echo SAME $(wc -l < /root/scratch/sha_mine.txt) lines
