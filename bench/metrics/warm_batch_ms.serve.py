"""Serving frontend: median harness span around `flush()` over the window's
batches that hold only warm (trained-user) requests, in ms."""
import statistics


def read(info):
    t0, t1 = info["win"]
    spans = [b - a for n, a, b, at in info["rec"].spans
             if n == "flush" and a >= t0 and b <= t1 and at.get("cold") == 0]
    return 1e3 * statistics.median(spans) if spans else None
