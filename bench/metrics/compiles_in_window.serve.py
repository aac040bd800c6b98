"""Programs compiled or loaded from the compilation cache inside the
measured window, counted from JAX's backend-compile events. Should be 0."""


def read(run):
    return run.counters["compiles_in_window"]
