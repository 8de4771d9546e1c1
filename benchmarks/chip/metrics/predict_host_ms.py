"""Host time per cokriging request (ms): the request's wall time on the
client's side not covered by device busy time (request validation, the
FactorStatus read-back, dispatch and the read-back of the answer),
averaged over the requests."""
from chipbench.readers import per_span


def read(r):
    host = per_span(r, "request", lambda s: s.ns - r.trace.busy_ns(
        s.start, s.end))
    return None if host is None else host / 1e6
