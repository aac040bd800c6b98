"""Host milliseconds per window inside the harness's ``feed`` spans: the
session's host work (coercion, padding, dispatch) for each window fed."""


def read(run):
    if run.trace is None or "feed" not in run.trace.spans:
        return None
    _, seconds = run.trace.spans["feed"]
    return seconds * 1e3 / run.counters["windows"]
