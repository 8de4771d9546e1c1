"""Device operations per millisecond of device busy time (ops/ms), over
the traced window.

Each iteration of a loop on the device runs its body's operations again,
so a latency-bound loop shows as many short operations: this rate times
the busy milliseconds of an evaluation is the evaluation's operation
count.  The count of one whole evaluation cannot be read where the
device's trace buffer (about 6 million events) fills up inside it, as it
does in an f64 TLR evaluation.
"""


def read(r):
    w = r.trace.window()
    if w is None:
        return None
    busy = r.trace.busy_ns(w.start, w.end)
    return r.trace.op_count(w.start, w.end) / (busy / 1e6) if busy else None
