"""Share of the chips' peak FLOP/s that the window's sweeps achieved, with
the work the algorithm needs (bench/work.py: statistics 2 x ratings x 2K^2,
solves rated entities x (K^3/3 + 2K^2)) over the sweeps' wall time."""


def read(info):
    layer = info["layer"]
    if not layer.get("sweeps") or not layer.get("sweep_seconds"):
        return None
    rate = layer["sweep_flops"] * layer["sweeps"] / layer["sweep_seconds"]
    return 100.0 * rate / (info["chips"] * info["peaks"]["flops_per_s"])
