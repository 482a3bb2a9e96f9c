"""Bytes the softmax cross-entropy needs: the logits are read once forward,
and read once and their gradient written once backward, in the type the model
hands them over in (float32 from ``apply_bert``); per row a label, a loss and
a log-sum-exp. Memory-bound: an exponential and a few operations per logit.
"""

import re


def touches_logits(hlo_text: str, rows: int, vocab: int) -> bool:
    """Whether an instruction has an operand or result of ``[rows, vocab
    padded to a lane multiple]``: how the readers tell this kernel's calls
    from LayerNorm's while the program gives its kernels no stable name."""
    return any(int(r) == rows and vocab <= int(c) < vocab + 1024
               for r, c in re.findall(r"\[(\d+),(\d+)\]", hlo_text))


def bytes_needed(rows: int, vocab: int, logit_bytes: int = 4) -> dict:
    fwd = rows * vocab * logit_bytes + rows * (4 + 4 + 4)
    bwd = 2 * rows * vocab * logit_bytes + rows * (4 + 4 + 4)
    return {"fwd": fwd, "bwd": bwd}

