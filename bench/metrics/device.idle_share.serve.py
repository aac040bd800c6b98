"""Share of the traced window in which no operation ran on the device,
averaged over the chips used."""


def read(run):
    if run.trace is None:
        return None
    t = run.trace
    return 100.0 * (1.0 - sum(t.busy_s) / len(t.busy_s) / t.window_s)
