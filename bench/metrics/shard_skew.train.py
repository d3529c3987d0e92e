"""Shard balance: the busiest chip's compute time in the traced window over
the mean compute time of the cell's chips, less 100, in %. Compute time is
the union of a chip's operations other than collectives: a chip that waits
for its ring neighbour waits inside a collective, so counting collectives
would even out the very skew this reads. The ring moves at the pace of
its slowest shard."""
import tracereduce


def read(info):
    tr, window = info["trace"], info["window"]
    if tr is None or window is None or len(tr.devices) < 2:
        return None
    busy = [tracereduce.length(tracereduce.union(
                [(a, b) for name, a, b in ops if not tracereduce.is_collective(name)],
                window))
            for ops in tr.devices.values()]
    mean = sum(busy) / len(busy)
    if mean <= 0:
        return None
    return 100.0 * (max(busy) / mean - 1.0)
