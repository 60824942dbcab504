"""Algorithmic HBM bytes (4 B read, 1 B written per true pixel) of the
requests launched in the traced window, over the device time of every
module launched in it, as a share of the peak bandwidth in peaks.json.
Bandwidth is the bound: Canny's few dozen flops a pixel sit far under the
chip's flops per byte."""

from bench import measures


def read(rec):
    return measures.roofline_pct(rec)
