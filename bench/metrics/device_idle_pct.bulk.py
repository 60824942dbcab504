"""Share of the traced window in which no op ran on the device."""

from bench import measures


def read(rec):
    return measures.idle_pct(rec)
