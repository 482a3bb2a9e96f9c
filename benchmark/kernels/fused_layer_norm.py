"""Bytes the LayerNorm algorithm needs (it is bound by memory, its
operations are a few per element): the minimal streams and nothing else.

forward:  read x, write y                        2 * rows * hidden * act
          read weight and bias, write mean+rstd  2 * hidden * 4 + 2 * rows * 4
backward: read x and dy, write dx                3 * rows * hidden * act
          read weight, mean, rstd; write dweight and dbias
                                                 3 * hidden * 4 + 2 * rows * 4

``act`` is the bytes of an activation element as the model holds it
(bfloat16 under amp O2). A kernel that moves more (float32 copies, a second
pass) gets a lower share of the roofline, as it should; counting its extra
streams as needed bytes is how a share passes 100%.
"""


def bytes_needed(rows: int, hidden: int, act_bytes: int = 2) -> dict:
    fwd = 2 * rows * hidden * act_bytes + 2 * hidden * 4 + 2 * rows * 4
    bwd = 3 * rows * hidden * act_bytes + 3 * hidden * 4 + 2 * rows * 4
    return {"fwd": fwd, "bwd": bwd}

