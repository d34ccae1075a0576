"""Mean harness-clock time of one submit() call in the traced interval:
admission, routing and enqueue on the caller's thread (the tail cells)."""

from metrics import _layer


def read(m):
    return _layer.submit_us(m)
