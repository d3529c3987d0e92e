"""Exchange: per sweep of the traced window, the time in which a collective
(`collective-permute`, `all-gather`, `all-reduce`, ...) ran on a chip and
no other operation of that chip did (`tracereduce.exposed_collective_seconds`),
averaged over the cell's chips, in ms. What the exchange adds to a sweep
that the statistics and the solve do not hide."""
import tracereduce


def read(info):
    tr, window = info["trace"], info["window"]
    sweeps = info["layer"].get("sweeps")
    if tr is None or window is None or not sweeps:
        return None
    if not any(tracereduce.is_collective(name) and a < window[1] and b > window[0]
               for ops in tr.devices.values() for name, a, b in ops):
        return None
    return 1e3 * tracereduce.exposed_collective_seconds(tr, window) / sweeps
