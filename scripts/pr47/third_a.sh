#!/bin/bash
# the parent on the new cell, and one pair of the DeepSeek cell
bash scripts/pr47/old_cells.sh 900 deepseek_v3.resident_context_decode:2147483711
