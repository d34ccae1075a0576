"""Median latency of the requests due in the window, from when each was
due to when its ticket was seen done (unanswered: infinite)."""

import numpy as np


def read(m):
    return float(np.percentile(m.lat_ms, 50)) if len(m.lat_ms) else None
