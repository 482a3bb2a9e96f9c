#!/bin/bash
# sha256 of the lowered text of every tiny configuration's serving programs
# (sha.py: GPT, hybrid, Nemotron, DeepSeek, K-EXAONE, Ling; float32 and bfloat16
# pools; prefill and decode, and the GPT family's verify, tree verify and
# chunk prefill): a parent checkout against the working tree. A change that
# says it leaves the served programs alone has to print SAME.
#   bash scripts/shacmp.sh [a checkout of the parent]
# With no argument the parent is HEAD, unpacked anew into .chip_tree/ (which
# .gitignore lists). Both lists go to chiprun_out/lowered/; the working
# tree's is kept beside this script as lowered_sha256.txt.
here=$(cd "$(dirname "$0")/.." && pwd)
parent=$1
if [ -z "$parent" ]; then
  parent=$here/.chip_tree/parent_head
  rm -rf "$parent" && mkdir -p "$parent" \
    && git -C "$here" archive HEAD | tar -x -C "$parent" || exit 1
fi
out=$here/chiprun_out/lowered; mkdir -p "$out"
sha() { (cd "$1" && PYTHONPATH=$1 JAX_PLATFORMS=cpu python "$here/scripts/sha.py" 2>&1 | grep -v "^E0\|^E1"); }
sha "$parent" > "$out/sha_parent.txt"
sha "$here" > "$out/sha_mine.txt"
diff "$out/sha_parent.txt" "$out/sha_mine.txt" || exit 1
cp "$out/sha_mine.txt" "$here/scripts/lowered_sha256.txt"
echo SAME $(wc -l < "$out/sha_mine.txt") lines
