"""The work the algorithm needs, from true shapes alone.

Canny reads each pixel of the float32 image once (4 B) and writes one
uint8 edge flag (1 B); its few dozen flops a pixel are far under the
chip's ratio of flops to bytes, so HBM bandwidth is the bound. Padding,
phantom batch slots and the implementation's intermediate maps are not
the algorithm's work and are not counted.
"""

from __future__ import annotations

import json
import pathlib

READ_BYTES_PER_PX = 4
WRITE_BYTES_PER_PX = 1

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def canny_bytes(shapes) -> int:
    """HBM bytes Canny must move for images of the given (h, w) shapes."""
    return sum(int(h) * int(w) for h, w in shapes) * (READ_BYTES_PER_PX + WRITE_BYTES_PER_PX)


def peaks(device_kind: str, path: pathlib.Path = PEAKS) -> dict:
    """The published peaks of one chip of ``device_kind``; a device missing
    from the table is an error, never a default."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path.name}")
    return table[device_kind]
