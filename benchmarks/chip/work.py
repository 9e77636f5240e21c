"""Bytes the algorithm needs, computed from sizes alone.

Never from a layout's padded slots: the same graph reads the same number
whatever layout carries it, so a layout that cuts padding shows as a
higher share of the peak.
"""


def sweep_bytes(n: int, nnz: int) -> int:
    """HBM bytes of one f32 power-iteration sweep over ``nnz`` stored
    edges and ``n`` vertices: per edge its f32 value, its int32 column
    index and the f32 rank it gathers; per vertex four f32 vector passes
    (read the ranks, write the new ranks, and read both again for the L1
    step)."""
    return nnz * (4 + 4 + 4) + n * 16
