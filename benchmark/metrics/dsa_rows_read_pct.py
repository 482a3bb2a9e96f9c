"""The share of its context the sparse attention attends: the program's own
counters over the window, ``dsa_rows_read`` (the positions each active slot's
query attended: the picked groups, its tail and itself) over
``dsa_rows_mapped`` (the positions it could have: the context and itself),
both summed over slots, sparse layers and decode steps on the device. 100
means that the selection is not engaged (every context at or under
``index_topk``). Nothing is reported for a program without the counters."""


def read(run):
    dsa = run["counts"].get("dsa")
    if not dsa or not dsa.get("rows_mapped"):
        return None
    return 100.0 * dsa["rows_read"] / dsa["rows_mapped"]
