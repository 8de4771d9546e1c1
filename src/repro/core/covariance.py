"""Cross-covariance matrix assembly (Section 5.2 of the paper).

Builds the ``pn x pn`` matrix Sigma(theta) from the parsimonious multivariate
Matérn under the two layouts of Fig. 3:

* Representation I  — n x n grid of p x p blocks (variables interleaved per
  location).  Combined with Morton ordering of the locations this is the
  layout the paper uses for TLR (rank decay of off-diagonal tiles).
* Representation II — p x p grid of n x n blocks (variable-major).

Also provides the prediction cross-covariance c0 (Eq. 4) and Morton (Z-order)
sorting of 2-D locations.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import linalg
from .matern import matern_correlation, parsimonious_nu_matrix, parsimonious_rho


class MaternParams(NamedTuple):
    """theta for the parsimonious multivariate Matérn.

    sigma2: (p,) marginal variances sigma_ii^2
    a:      scalar spatial range
    nu:     (p,) marginal smoothnesses nu_ii
    beta:   (p, p) symmetric latent correlation matrix (diag == 1)
    """

    sigma2: jax.Array
    a: jax.Array
    nu: jax.Array
    beta: jax.Array

    @property
    def p(self) -> int:
        return self.sigma2.shape[0]

    @staticmethod
    def bivariate(sigma11=1.0, sigma22=1.0, a=0.1, nu11=0.5, nu22=1.0, beta=0.5,
                  dtype=jnp.float64):
        b = jnp.array([[1.0, beta], [beta, 1.0]], dtype)
        return MaternParams(jnp.array([sigma11, sigma22], dtype),
                            jnp.asarray(a, dtype),
                            jnp.array([nu11, nu22], dtype), b)

    @staticmethod
    def trivariate(sigma2=(1.0, 1.0, 1.0), a=0.1, nu=(0.5, 1.0, 1.5),
                   beta12=0.5, beta13=0.3, beta23=0.2, dtype=jnp.float64):
        b = jnp.array([[1.0, beta12, beta13],
                       [beta12, 1.0, beta23],
                       [beta13, beta23, 1.0]], dtype)
        return MaternParams(jnp.asarray(sigma2, dtype), jnp.asarray(a, dtype),
                            jnp.asarray(nu, dtype), b)

    @staticmethod
    def univariate(sigma2=1.0, a=0.1, nu=0.5, dtype=jnp.float64):
        return MaternParams(jnp.array([sigma2], dtype), jnp.asarray(a, dtype),
                            jnp.array([nu], dtype), jnp.ones((1, 1), dtype))


def pairwise_distances(locs_a, locs_b=None):
    """Euclidean distances between location sets ((na, d), (nb, d)) -> (na, nb)."""
    locs_a = jnp.asarray(locs_a)
    locs_b = locs_a if locs_b is None else jnp.asarray(locs_b)
    d2 = jnp.sum((locs_a[:, None, :] - locs_b[None, :, :]) ** 2, axis=-1)
    return jnp.sqrt(jnp.maximum(d2, 0.0))


def _concrete_halfint(nu):
    """float(nu) if it is a concrete half-integer with a closed form."""
    try:
        v = float(nu)
    except (TypeError, jax.errors.TracerArrayConversionError,
            jax.errors.ConcretizationTypeError):
        return None
    return v if v in (0.5, 1.5, 2.5) else None


def _general_orders(u, nu_ij, pairs):
    """{(i, j): M_{nu_ij}(u)} for the pairs without a closed form, from ONE
    vectorized K_nu call: the compiled program then holds one copy of its
    series and continued-fraction loops instead of one per pair (each copy
    of the f64 loops takes ~45 s to compile for v5e)."""
    if not pairs:
        return {}
    nus = jnp.stack([nu_ij[i, j] for i, j in pairs])
    corr = matern_correlation(u, nus.reshape((-1,) + (1,) * u.ndim))
    return {ij: corr[g] for g, ij in enumerate(pairs)}


def _pair_correlations(dists, params: MaternParams, d_spatial: int = 2):
    """Correlation stack for every ordered variable pair.

    Returns (p, p, *dists.shape): rho_ij * M_{nu_ij}(h / a).  The diagonal
    carries the marginal correlations (rho_ii = 1).

    Concrete half-integer orders take the closed-form path (exp/mul only) —
    this is the production hot path: the general-K_nu while_loop carries
    (n, n) f32 buffers that GSPMD replicates on every device (measured in the
    dry-run: 2 x 68 GB per chip at n = 131k before this fast path).
    """
    from .matern import matern_correlation_halfint

    p = params.p
    nu_ij = parsimonious_nu_matrix(params.nu)
    rho = parsimonious_rho(params.nu, params.beta, d=d_spatial)
    u = dists / params.a

    # Only p(p+1)/2 distinct orders; evaluate each once then mirror.
    pairs = list(zip(*np.triu_indices(p)))
    halves = {ij: _concrete_halfint(nu_ij[ij]) for ij in pairs}
    vals = _general_orders(u, nu_ij, [ij for ij in pairs if halves[ij] is None])
    corr = jnp.zeros((p, p) + dists.shape,
                     dtype=jnp.result_type(u.dtype, jnp.float32))
    for i, j in pairs:
        c = vals.get((i, j))
        if c is None:
            c = matern_correlation_halfint(u, halves[i, j])
        corr = corr.at[i, j].set(c)
        if i != j:
            corr = corr.at[j, i].set(c)
    return rho[(...,) + (None,) * dists.ndim] * corr


# Locations per column panel when build_sigma assembles a large Sigma.
_PANEL_LOCS = 512


@jax.named_scope("repro.gen")
def build_sigma(locs, params: MaternParams, representation: str = "I",
                d_spatial: int = 2, nugget: float | None = None, dists=None):
    """Assemble Sigma(theta) of shape (p*n, p*n).

    representation "I": entry ((l, i), (r, j)) at [l*p + i, r*p + j]
    representation "II": at [i*n + l, j*n + r]
    """
    p = params.p
    rep = representation.upper()
    if rep not in ("I", "II"):
        raise ValueError(f"unknown representation {representation!r}")
    n = (locs if dists is None else dists).shape[0]
    if (dists is None and rep == "I" and n > _PANEL_LOCS
            and n % _PANEL_LOCS == 0 and linalg._on_tpu()):
        # Column panels under a fori_loop: the K_nu loops then carry
        # (p, p, n, _PANEL_LOCS) arrays instead of (p, p, n, n) ones, which
        # keeps the dense m = 8192 program inside a v5e's HBM.
        locs = jnp.asarray(locs)
        nb = _PANEL_LOCS * p

        def panel(j, sigma):
            col = build_sigma_column(locs, j, _PANEL_LOCS, params,
                                     d_spatial=d_spatial)
            return jax.lax.dynamic_update_slice(sigma, col, (0, j * nb))

        dtype = jnp.result_type(locs.dtype, params.sigma2.dtype, jnp.float32)
        sigma = jax.lax.fori_loop(0, n // _PANEL_LOCS, panel,
                                  jnp.zeros((n * p, n * p), dtype))
    else:
        if dists is None:
            dists = pairwise_distances(locs)
        sig = jnp.sqrt(params.sigma2)
        amp = sig[:, None] * sig[None, :]
        blocks = _pair_correlations(dists, params, d_spatial)  # (p, p, n, n)
        blocks = amp[:, :, None, None] * blocks
        # (p, p, n, n) -> (n, p, n, p) [I] or (p, n, p, n) [II] -> (np, np)
        perm = (2, 0, 3, 1) if rep == "I" else (0, 2, 1, 3)
        sigma = jnp.transpose(blocks, perm).reshape(n * p, n * p)
    # `is not None`, never truthiness: the MLE traces the nugget (spmdlint A1).
    if nugget is not None:
        idx = jnp.arange(n * p)
        sigma = sigma.at[idx, idx].add(nugget)
    return sigma


def build_sigma_panel(locs_rows, locs_cols, params: MaternParams,
                      d_spatial: int = 2, gen: str = "xla", block: int = 256):
    """Assemble one Representation-I covariance panel between two location
    sets without ever materializing the full Sigma.

    Returns the (R*p, C*p) interleaved block whose entry
    [l*p + i, r*p + j] = C_ij(rows[l] - cols[r]); slicing ``build_sigma``'s
    output to the same row/column ranges gives the identical values.  This is
    the paper's GEN phase (Figs. 10-11): HiCMA/STARS-H hand each tile worker
    the *generator*, not the matrix.

    ``gen="pallas"`` routes concrete half-integer pair smoothnesses through
    the ``kernels.matern_tile`` Pallas kernel where it compiles (f32, or
    the interpreter off TPU); general (or traced) orders and f64 locations
    on TPU take the XLA path, so the knob is always safe to set.
    """
    from .matern import matern_correlation_halfint

    locs_rows = jnp.asarray(locs_rows)
    locs_cols = jnp.asarray(locs_cols)
    R, C = locs_rows.shape[0], locs_cols.shape[0]
    p = params.p
    nu_ij = parsimonious_nu_matrix(params.nu)
    rho = parsimonious_rho(params.nu, params.beta, d=d_spatial)
    sig = jnp.sqrt(params.sigma2)
    amp = rho * (sig[:, None] * sig[None, :])
    inv_a = 1.0 / params.a
    use_pallas = gen == "pallas" and locs_rows.shape[1] == 2
    if use_pallas:
        from ..kernels.matern_tile import compiles_for
        use_pallas = compiles_for(locs_rows.dtype)

    pairs = list(zip(*np.triu_indices(p)))
    halves = {ij: _concrete_halfint(nu_ij[ij]) for ij in pairs}
    general = [ij for ij in pairs if halves[ij] is None]
    if general or not use_pallas:
        u = pairwise_distances(locs_rows, locs_cols) * inv_a
    vals = _general_orders(u, nu_ij, general) if general else {}
    corr = jnp.zeros((p, p, R, C),
                     dtype=jnp.result_type(locs_rows.dtype, jnp.float32))
    for i, j in pairs:
        c = vals.get((i, j))
        if c is None and use_pallas:
            from ..kernels.matern_tile import matern_tile
            c = matern_tile(locs_rows, locs_cols, inv_a, 1.0,
                            nu=halves[i, j], block_n=block, block_m=block)
        elif c is None:
            c = matern_correlation_halfint(u, halves[i, j])
        corr = corr.at[i, j].set(c)
        if i != j:
            corr = corr.at[j, i].set(c)
    blocks = amp[:, :, None, None] * corr
    return jnp.transpose(blocks, (2, 0, 3, 1)).reshape(R * p, C * p)


def build_sigma_column(locs, j, nbl: int, params: MaternParams,
                       d_spatial: int = 2, gen: str = "xla", block: int = 256,
                       row_block: int = 0):
    """One Representation-I *tile-grid column* panel, generator-direct.

    Returns the (m, nb) slice ``build_sigma(locs, ...)[:, j*nb:(j+1)*nb]``
    (m = n*p, nb = nbl*p) without materializing Sigma.  ``j`` may be a traced
    tile-column index — the distributed compression loop
    (core.dist_tlr.dist_compress_tiles) runs it under lax.fori_loop — while
    ``nbl`` (locations per tile) must be static so the slice has a static
    shape.

    ``row_block`` > 0 generates the panel ``row_block`` locations at a time
    under a fori_loop: the K_nu loops then carry (p, p, row_block, nbl)
    arrays instead of (p, p, n, nbl) ones.  In one piece a v5e program
    needs 2.4 GiB of temporaries for them at n = 4096, nbl = 1024 in f64.
    """
    locs = jnp.asarray(locs)
    cols = jax.lax.dynamic_slice_in_dim(locs, j * nbl, nbl, axis=0)
    n, p = locs.shape[0], params.p
    if not (0 < row_block < n and n % row_block == 0):
        return build_sigma_panel(locs, cols, params, d_spatial=d_spatial,
                                 gen=gen, block=block)

    def rows(i, out):
        lr = jax.lax.dynamic_slice_in_dim(locs, i * row_block, row_block, 0)
        pan = build_sigma_panel(lr, cols, params, d_spatial=d_spatial,
                                gen=gen, block=block)
        return jax.lax.dynamic_update_slice(out, pan, (i * row_block * p, 0))

    dtype = jnp.result_type(locs.dtype, params.sigma2.dtype, jnp.float32)
    return jax.lax.fori_loop(0, n // row_block, rows,
                             jnp.zeros((n * p, nbl * p), dtype))


def build_correlation_matrix(locs, a, nu, nugget: float | None = None,
                             dists=None):
    """Univariate correlation matrix R_ii(theta_i) (profile-likelihood path)."""
    if dists is None:
        dists = pairwise_distances(locs)
    r = matern_correlation(dists / a, nu)
    if nugget is not None:
        r = r + nugget * jnp.eye(dists.shape[0], dtype=r.dtype)
    return r


def build_c0(pred_locs, obs_locs, params: MaternParams, representation: str = "I",
             d_spatial: int = 2):
    """Prediction cross-covariance (Eq. 4) for a batch of prediction points.

    Returns (npred, p*n, p): c0 for each prediction location, rows ordered to
    match ``build_sigma``'s representation.
    """
    dists = pairwise_distances(pred_locs, obs_locs)  # (npred, n)
    p = params.p
    npred, n = dists.shape
    sig = jnp.sqrt(params.sigma2)
    amp = sig[:, None] * sig[None, :]
    blocks = _pair_correlations(dists, params, d_spatial)  # (p, p, npred, n)
    blocks = amp[:, :, None, None] * blocks
    # entry (i, j, l, r) = C_ij(s0_l - s_r); c0 rows follow obs ordering.
    if representation.upper() == "I":
        # row (r*p + i), column j -> (npred, n, p_i, p_j) -> (npred, n*p, p)
        c0 = jnp.transpose(blocks, (2, 3, 0, 1)).reshape(npred, n * p, p)
    else:
        c0 = jnp.transpose(blocks, (2, 0, 3, 1)).reshape(npred, n * p, p)
    return c0


@jax.named_scope("repro.gen")
def build_c0_panels(obs_locs, pred_locs, params: MaternParams, *, nbl: int,
                    d_spatial: int = 2, gen: str = "xla"):
    """Prediction cross-covariance in *tile-panel* form, generator-direct.

    Returns (T, nb, B*p) with T = n // nbl tile rows and nb = nbl * p:
    tile t is the Representation-I panel between observation tile t and the
    whole prediction batch, i.e. ``out.reshape(m, B*p)`` equals the dense
    ``build_sigma_panel(obs_locs, pred_locs, ...)`` — the (m, B, p)
    transpose of ``build_c0``'s (B, m, p).  The serving decode path
    (serving/cokrige_service.py) streams these tiles against the cached
    TLR factor one observation tile at a time, so neither Sigma nor a
    dense (B, m, p) c0 is ever materialized for large B.

    ``nbl`` (locations per tile) must be static and divide n.  Tile rows
    are generated as one vmapped batch (the compress-GEN idiom — a scan
    with stacked outputs trips the SPMD partitioner's index-width checks
    when the result carries a sharding constraint under x64), so the
    leading axis shards cleanly over the row mesh axes.
    """
    obs_locs = jnp.asarray(obs_locs)
    pred_locs = jnp.asarray(pred_locs)
    n = obs_locs.shape[0]
    if n % nbl:
        raise ValueError(f"nbl={nbl} must divide n={n}")
    T = n // nbl

    gen_row = jax.vmap(lambda rows: build_sigma_panel(
        rows, pred_locs, params, d_spatial=d_spatial, gen=gen,
        block=nbl * params.p))
    return gen_row(obs_locs.reshape(T, nbl, -1))  # (T, nb, B*p)


def cross_cov_at_zero(params: MaternParams, d_spatial: int = 2):
    """C(0; theta) — the p x p colocated covariance."""
    rho = parsimonious_rho(params.nu, params.beta, d=d_spatial)
    sig = jnp.sqrt(params.sigma2)
    return rho * (sig[:, None] * sig[None, :])


# ---------------------------------------------------------------------------
# Morton (Z-order) ordering — improves off-diagonal tile rank decay (§5.3).
# ---------------------------------------------------------------------------


def _interleave_bits_u32(v: np.ndarray) -> np.ndarray:
    """Spread the lower 16 bits of v so there is a zero bit between each."""
    v = v.astype(np.uint64) & np.uint64(0xFFFF)
    v = (v | (v << np.uint64(8))) & np.uint64(0x00FF00FF)
    v = (v | (v << np.uint64(4))) & np.uint64(0x0F0F0F0F)
    v = (v | (v << np.uint64(2))) & np.uint64(0x33333333)
    v = (v | (v << np.uint64(1))) & np.uint64(0x55555555)
    return v


def morton_order(locs) -> np.ndarray:
    """Permutation sorting 2-D locations by Morton (Z-curve) code.

    Host-side preprocessing (numpy): quantizes each coordinate to 16 bits over
    its range and interleaves.  Returns the permutation indices.
    """
    locs = np.asarray(locs)
    assert locs.ndim == 2 and locs.shape[1] == 2, "morton_order expects (n, 2)"
    lo = locs.min(axis=0)
    hi = locs.max(axis=0)
    span = np.where(hi > lo, hi - lo, 1.0)
    q = np.clip(((locs - lo) / span * 65535.0).astype(np.uint64), 0, 65535)
    code = _interleave_bits_u32(q[:, 0]) | (
        _interleave_bits_u32(q[:, 1]) << np.uint64(1))
    return np.argsort(code, kind="stable")


def apply_ordering(locs, perm):
    return jnp.asarray(np.asarray(locs)[np.asarray(perm)])
