"""Share of the fit window's device leaf time in the program's
``repro.gen`` scope (%); see ``chipbench/scopes.py``."""
from chipbench.scopes import fit_share


def read(r):
    return fit_share(r, "gen")
