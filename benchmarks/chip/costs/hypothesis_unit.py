"""Bytes of each `hypothesis_unit` call in one fused step.

Each decoded frame calls the kernel once over the step's `b` slot rows.
A row holds N = K * (2C + 1) candidates (stay, continue and commit per
hypothesis): the kernel reads their sorted keys (int32) and the two CTC
channels (float32), and writes K selected positions, two channels and a
valid flag (4 bytes each).  Its work is compares, selects and row
reductions on the vector unit, with no matrix multiply, and no vector
peak is published for the chip: so it is held against the memory roof
alone (flops 0), and its share says how far it is from streaming its
operands at full bandwidth."""
from __future__ import annotations


def calls(sizes: dict, dec: dict, b: int, w: int) -> list:
    """[(flops, bytes)] of the step's hypothesis-unit calls."""
    k = dec["beam_size"]
    n = k * (2 * dec["max_children"] + 1)
    nbytes = 4 * (3 * b * n + 4 * b * k)
    return [(0.0, float(nbytes))] * w
