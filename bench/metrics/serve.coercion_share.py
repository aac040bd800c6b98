"""Share of the window the service's ingest thread spent coercing and
merging chunks (``PartitionService``'s ``coercion_s``)."""


def read(run):
    return 100.0 * run.counters["coercion_s"] / run.counters["window_s"]
