"""Device placement for the BPMF launchers: the serving tier's host
devices, a small mesh over the local devices, and the chip's published peaks.

The device lists and the mesh are built by functions, not module-level
constants, so importing this module never touches jax device state.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np
import jax


def serving_host_devices(*, mesh=None, n_hosts: int | None = None) -> list:
    """Lead devices for the multi-host serving tier (serve/cluster.py),
    one per shard host.

    With a mesh: hosts follow the *slowest* fabric boundary — one host per
    "pod" slice on a multi-pod mesh (the inter-pod links are where a
    resident shard + routed U replica beat shipping score traffic), else
    one per "data" row. Each host's lead device is the first device of its
    slice; its V' shard and U replica are placed there.

    Without a mesh: the first `n_hosts` local devices. On the CPU backend
    (the `--xla_force_host_platform_device_count` simulation path) hosts
    cycle over the devices when fewer exist than requested; on any other
    backend fewer devices than hosts is an error, so two hosts never share
    one accelerator without anyone asking for it.
    """
    if mesh is not None:
        axis = "pod" if "pod" in mesh.axis_names else mesh.axis_names[0]
        k = mesh.axis_names.index(axis)
        devs = mesh.devices
        # one lead device per index along the host axis
        return [
            np.take(devs, i, axis=k).flatten()[0]
            for i in range(devs.shape[k])
        ]
    devices = jax.devices()
    if n_hosts is None:
        n_hosts = len(devices)
    if n_hosts > len(devices) and jax.default_backend() != "cpu":
        raise RuntimeError(
            f"{n_hosts} serving hosts need {n_hosts} devices, have "
            f"{len(devices)} {jax.default_backend()} device(s)"
        )
    return [devices[i % len(devices)] for i in range(n_hosts)]


def make_host_mesh(model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    model = max(1, min(model, n))
    data = n // model
    return jax.make_mesh((data, model), ("data", "model"),
                         devices=jax.devices()[: data * model])


class ChipPeaks(NamedTuple):
    flops_bf16: float   # FLOP/s per chip
    hbm_bw: float       # HBM bytes/s per chip
    ici_bw: float       # interconnect bytes/s per link


# Published peaks per chip, keyed by `jax.devices()[0].device_kind`.
# Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB
# HBM at 819 GB/s, 1,600 Gbit/s of inter-chip interconnect (4 links).
CHIP_PEAKS = {
    "TPU v5 lite": ChipPeaks(flops_bf16=197e12, hbm_bw=819e9, ici_bw=50e9),
}

#: the production target the dry-run models (launch/bpmf_dryrun.py)
TARGET_KIND = "TPU v5 lite"


def chip_peaks(kind: str) -> ChipPeaks:
    """Peaks of one chip of `kind`; a kind not in the table is an error,
    never a default."""
    try:
        return CHIP_PEAKS[kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {kind!r}; known: "
            f"{sorted(CHIP_PEAKS)}"
        ) from None
