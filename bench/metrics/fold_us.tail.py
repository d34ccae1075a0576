"""Device time of the fold_endpoint programs in the traced interval per
query answered in it."""

from metrics import _layer


def read(m):
    return _layer.entry_us(m, "fold_endpoint")
