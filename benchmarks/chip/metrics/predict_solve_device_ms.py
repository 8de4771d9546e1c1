"""Device time per cokriging request in the program's ``repro.solve``
scope (ms): leaf operations inside each ``request`` span, averaged over
the requests; see ``chipbench/scopes.py``."""
from chipbench.scopes import request_ms


def read(r):
    return request_ms(r, "solve")
