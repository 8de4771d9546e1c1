"""Algorithmic work of one exact log-likelihood evaluation, from shapes.

m = p n.  Sigma's lower triangle (L = m (m + 1) / 2 words) is generated,
factored in place by Cholesky (m^3 / 3 operations), and read once more by
the forward solve (m^2 operations) and the quadratic form (2 m).  Each of
the L words moves four times (written by the generator, read and written
by the factorization, read by the solve), 8 bytes each in float64.  K_nu
evaluations in the generator are not counted as operations, so the
roofline share taken from this is a lower bound.

It supersedes ``launch/roofline.py``'s ``geostat_model_flops`` and its
single-constant peaks for any claim: those stay the dry run's model.
"""


def work(cfg: dict) -> tuple:
    """(operations, bytes) of one evaluation."""
    m = cfg["p"] * cfg["grid"][0] * cfg["grid"][1]
    words = m * (m + 1) // 2
    return m ** 3 / 3 + m ** 2 + 2 * m, 4 * 8 * words
