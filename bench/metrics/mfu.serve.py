"""Whole serving window's share of the chip's peak FLOP/s: the scoring and
fold-in work the served requests need (bench/work.py) over the window."""


def read(info):
    flops = info["layer"].get("served_flops")
    t0, t1 = info["win"]
    if not flops or t1 <= t0:
        return None
    return 100.0 * flops / (t1 - t0) / (info["chips"] * info["peaks"]["flops_per_s"])
