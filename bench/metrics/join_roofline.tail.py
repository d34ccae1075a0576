"""The join's analytic work (work.join) over the device time of its
programs, as a share of the chip's roofline (peaks.json)."""

from metrics import _layer


def read(m):
    return _layer.roofline(m, "join", "join_endpoints")
