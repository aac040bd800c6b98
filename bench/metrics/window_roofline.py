"""Share of the HBM roofline the window programs reach: the bytes the
window's events need, over the chip's peak bandwidth, over the busiest
chip's busy time in the traced window.

The bytes are what the events themselves need, counted from shapes: each
event's input row, its own row written (add) or read (vertex delete), the
labels and presence of its row's neighbours, both rows of an edge delete
read and written, and the K-sized state read and written once per window.
Passes over the whole vertex range that an implementation adds are not
needed by any event and are not counted.
"""
import numpy as np

from bench import traffic
from bench.peaks import peak


def window_bytes(etype: np.ndarray, *, max_deg: int, k_max: int,
                 window: int) -> int:
    """Bytes the events ``etype`` need, fed in windows of ``window``."""
    d, k = max_deg, k_max
    row, label = 4 * d, 5 * d            # int32 ids; int32 label + bool
    event_in = 8 + row                   # code, vertex, row
    per_type = {
        traffic.EVENT_ADD: event_in + row + label + 5,
        traffic.EVENT_DEL_VERTEX: event_in + row + label + 5,
        traffic.EVENT_DEL_EDGE: event_in + 4 * row + 10,
    }
    k_state = 4 * k + 4 * k + k + 4 * k * k + 4 * 6 + 8
    windows = -(-etype.size // window)
    counts = np.bincount(etype, minlength=3)
    return int(sum(int(counts[t]) * b for t, b in per_type.items())
               + windows * 2 * k_state)


def read(run):
    if run.trace is None:
        return None
    s = run.session_cfg
    lo, hi = run.counters["window_begin"], run.counters["window_end"]
    need = window_bytes(run.stream.etype[lo:hi], max_deg=s["max_deg"],
                        k_max=s["engine"]["k_max"], window=s["window"])
    bw = peak(run.devices[0].device_kind)["hbm_bytes_per_s"]
    return 100.0 * need / bw / max(run.trace.busy_s)
