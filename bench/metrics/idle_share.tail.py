"""Share of the traced interval in which no operation ran on the device
(the tail cells)."""

from metrics import _layer


def read(m):
    return _layer.idle_share(m)
