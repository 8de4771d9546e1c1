"""One likelihood evaluation's share of its roofline (%).

The least time is the larger of the evaluation's algorithmic operations
over the device's peak rate and its bytes over HBM bandwidth, both from
the backend's work model (``work/<backend>.py``) at the configuration's
shapes.  It is divided by the device busy time of one evaluation, the
mean over the whole evaluations that the traced slice holds.  A slice
inside one evaluation, as in an f64 TLR evaluation longer than the
device's trace buffer holds, has none, and the metric is not read.  The
peak is the bf16 rate, the only one v5e publishes, so for an f64
evaluation the share is a lower bound on the true one.
"""


def read(r):
    w = r.trace.window()
    if w is None or r.work is None:
        return None
    evals = [s for s in r.trace.covered("eval")
             if w.start <= s.start and s.end <= w.end]
    busy = sum(r.trace.busy_ns(s.start, s.end) for s in evals)
    if not busy:
        return None
    ops, nbytes = r.work
    least = max(ops / r.peaks["bf16_flops_per_s"],
                nbytes / r.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (busy / len(evals) / 1e9)
