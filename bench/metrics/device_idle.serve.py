"""Serving window's device idle share: 1 - (union of operation intervals
/ traced window), averaged over the cell's devices."""
import tracereduce


def read(info):
    tr, window = info["trace"], info["window"]
    if tr is None or window is None or not info["layer"].get("served"):
        return None
    return 100.0 * (1.0 - tracereduce.busy_seconds(tr, window) / (window[1] - window[0]))
