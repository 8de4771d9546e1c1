"""Device busy time per cokriging request (ms): the union of device
operations inside each ``request`` span, averaged over the requests."""
from chipbench.readers import device_busy_s


def read(r):
    busy = device_busy_s(r, "request")
    return None if busy is None else busy * 1e3
