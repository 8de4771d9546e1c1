"""Share of the serving window in which the device ran nothing (%)."""
from chipbench.readers import idle_share


def read(r):
    return idle_share(r)
