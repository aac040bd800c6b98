"""Device milliseconds per window: the busiest chip's busy time in the
traced window (the union of its operations' intervals) over the windows
fed in it."""


def read(run):
    if run.trace is None:
        return None
    return max(run.trace.busy_s) * 1e3 / run.counters["windows"]
