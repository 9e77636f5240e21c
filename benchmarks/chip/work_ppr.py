"""Bytes the batched personalized sweeps need, computed from sizes alone,
as ``work.py`` counts those of the single-vector sweep: never from padded
slots or padded lanes, so a layout or a batch width that cuts padding
shows as a higher share of the peak."""


def batched_sweep_bytes(n: int, nnz: int, sweeps: int,
                        column_sweeps: int) -> int:
    """HBM bytes of ``sweeps`` batched (N, Q) sweeps over ``nnz`` stored
    edges and ``n`` vertices that swept ``column_sweeps`` query columns in
    all (each sweep adds its number of queries): per sweep, each edge's f32
    value and int32 column index, read once for the whole batch; per
    column swept, the f32 rank each edge gathers and four f32 vector passes
    per vertex."""
    return 8 * nnz * sweeps + (4 * nnz + 16 * n) * column_sweeps
