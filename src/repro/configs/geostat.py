"""The paper's own workloads as first-class --arch configs."""
from .base import GEOSTAT_SHAPES, GeoStatConfig

GEOSTAT_EXACT = GeoStatConfig(
    name="geostat-exact",
    backend="exact",
    tile_size=4096,              # GSPMD panel width
    shapes=tuple(GEOSTAT_SHAPES),
)

GEOSTAT_TLR = GeoStatConfig(
    name="geostat-tlr",
    backend="tlr",
    tile_size=2048,              # nb = O(sqrt(pn)) trade-off (paper §5.3)
    max_rank=256,                # TLR7 ranks of neighbouring tiles reach
                                 # ~220 at range a = 0.03; 128 truncated
                                 # singular values of ~1e-4 (PERF.md)
    tol=1e-7,                    # TLR7 default
    block_cyclic=True,           # pair-batch factorization (the §Perf form;
                                 # --tlr-block-cyclic 0 re-runs the masked
                                 # full-grid baseline)
    shapes=tuple(GEOSTAT_SHAPES),
)
