"""Algorithmic work of one TLR log-likelihood evaluation, from shapes.

m = p n, nb the tile size, T = m / nb tiles a side, k = max_rank: the
configuration's shapes, not the ranks a run reaches, so the count stays
the same whatever implements the layers.  Operations (Akbudak et al. 2017,
the right-looking TLR Cholesky the paper uses):

* compress: each of the T (T - 1) / 2 off-diagonal tiles to rank k by a
  randomized range (sketch and projection, 2 x 2 nb^2 k);
* at panel step j: POTRF of the diagonal tile (nb^3 / 3); TRSM of the k
  columns of each tile below it (nb^2 k); for each tile i below it the
  diagonal update A_ii -= U (V^T V) U^T (2 x 2 nb k^2 + 2 nb^2 k); for each
  pair i > l > j the off-diagonal update U_ij (V_ij^T V_lj) U_lj^T
  (2 x 2 nb k^2) and its recompression from rank 2k to k: two QRs of
  nb x 2k, an SVD of the 2k x 2k core (22 (2k)^3) and two nb x 2k x k
  products;
* the forward solve (T nb^2 + 4 nb k per off-diagonal tile) and the
  quadratic form.

Bytes: the stored words, T diagonal lower triangles and 2 nb k per
off-diagonal tile, each moved four times (written by generation and
compression, read and written by the factorization, read by the solve),
8 bytes each.  Generation's K_nu is not counted as operations, so the
roofline share taken from this is a lower bound.

It supersedes ``launch/roofline.py``'s ``geostat_model_flops`` and its
single-constant peaks for any claim: those stay the dry run's model.
"""


def qr(n: int, c: int) -> float:
    return 2 * n * c ** 2 - 2 * c ** 3 / 3


def work(cfg: dict) -> tuple:
    """(operations, bytes) of one evaluation."""
    m = cfg["p"] * cfg["grid"][0] * cfg["grid"][1]
    nb, k = cfg["tile_size"], cfg["max_rank"]
    t = m // nb
    off = t * (t - 1) // 2
    recompress = 2 * qr(nb, 2 * k) + 22 * (2 * k) ** 3 + 2 * (2 * nb * 2 * k * k)
    ops = off * 4 * nb ** 2 * k
    for j in range(t):
        below = t - 1 - j
        pairs = below * (below - 1) // 2
        ops += (nb ** 3 / 3 + below * nb ** 2 * k
                + below * (4 * nb * k ** 2 + 2 * nb ** 2 * k)
                + pairs * (4 * nb * k ** 2 + recompress))
    ops += t * nb ** 2 + off * 4 * nb * k + 2 * m
    words = t * nb * (nb + 1) // 2 + off * 2 * nb * k
    return ops, 4 * 8 * words
