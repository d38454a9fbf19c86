"""Operations of one sketched-KRR job: fit on n rows and predict n_test.

Counted from the algorithm (paper eq. 3 in Woodbury form), not from what an
implementation does: C = K S by the matrix-free kernel on the training rows
(``matfree_apply``), W = SᵀC from the m·d landmark rows of C (2·m·d·d),
the Grams CᵀC (2·n·d²) and Cᵀy (2·n·d), the Cholesky solve of the d×d
system (d³/3 + 2·d²), and the prediction K(X_test, landmarks)·S·θ.  The
in-sample fitted values are not part of the job's answer and are not
counted.  A whole job is compute-bound.
"""
from bench.counts import matfree_apply as matfree


def flops(n: int, n_test: int, p: int, d: int, m: int) -> float:
    return (matfree.work(n, p, d, m)["flops"]
            + 2.0 * m * d * d
            + 2.0 * n * d * d + 2.0 * n * d
            + d**3 / 3.0 + 2.0 * d * d
            + matfree.work(n_test, p, d, m)["flops"] + 2.0 * n_test * d)
