"""Events per batch the service dispatched in the window: how much
continuous batching coalesced (``PartitionService`` counters)."""


def read(run):
    return run.counters["events"] / run.counters["batches_dispatched"]
