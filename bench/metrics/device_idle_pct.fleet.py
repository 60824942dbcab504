"""Share of the traced window in which no op ran on the device, the
device's busy time averaged over the trace's devices."""

from bench import measures


def read(rec):
    return measures.idle_pct(rec)
