"""Work of one matrix-free kernel call K(Xq, landmarks)·S (``matfree_apply``).

For rows of width p against the m·d landmark rows, the algorithm needs the
distance GEMM (2·rows·p·m·d), the combination of the m slabs with the
sketch's coefficients (2·rows·m·d), and must read the rows, the landmarks
and the coefficients once and write C once, all float32.  The m·d kernel
values themselves (one exp each) are not counted as operations.  At the
KRR cell's sizes it is compute-bound: 2·p·m·d / (4·(p + d)) ≈ 330
operations per byte against the v5e's 240.
"""


def work(rows: int, p: int, d: int, m: int) -> dict:
    md = m * d
    return {
        "flops": 2.0 * rows * p * md + 2.0 * rows * md,
        "bytes": 4.0 * (rows * p + md * p + md + rows * d),
    }
