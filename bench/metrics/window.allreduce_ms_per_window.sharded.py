"""All-reduce milliseconds per window of the vertex-sharded session: the
busiest chip's all-reduce time in the traced window (the union of its
all-reduce operations' intervals: the window's two psums, of the prep
scan's emissions and of the halo labels) over the windows fed in it."""


def read(run):
    if run.trace is None:
        return None
    t = run.trace
    busiest = max(range(len(t.busy_s)), key=t.busy_s.__getitem__)
    return t.allreduce_s[busiest] * 1e3 / run.counters["windows"]
