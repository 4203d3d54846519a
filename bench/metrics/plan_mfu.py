"""Whole-plan share of the chips' int8 peak, in %.

2 x MACs per image (every layer, from the benchmark's own layer table)
x images returned in the traced window / (window x chips x int8 peak).
The int8 peak bounds every op of these <= 8-bit graphs, so the share
reads the same work whatever implements a segment.
"""


def read(record: dict):
    window = record.get("window_s")
    if not record.get("images") or not window:
        return None
    ops = 2.0 * record["macs_per_image"] * record["images"]
    return 100.0 * ops / (window * record["chips"]
                          * record["peaks"]["int8_ops_per_s"])
