"""Device time of the join_endpoint programs in the traced interval per
query answered in it."""

from metrics import _layer


def read(m):
    return _layer.entry_us(m, "join_endpoints")
