"""99th percentile of how late the load generator submitted a chunk after
its due time: a starved generator shows here, not as a fast server."""


def read(run):
    return run.counters["lag_p99_ms"]
