"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler builds each kernel for a chip that is
described, not attached, at the widths the ChEMBL / MovieLens deployments
use (K=64, bucket widths up to 512, catalogues of 5,775 and 27,278 items).
A kernel Mosaic refuses (an op it cannot lower, an unaligned slice, too
much VMEM) fails here at no chip time. The topology is described inside a
fixture, never at import: only one process may load the TPU library.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.gibbs import DeviceBucket, bucket_stats
from repro.kernels.bpmf_gather_syrk import LANES, gather_syrk_pallas
from repro.kernels.bpmf_syrk import masked_syrk_pallas
from repro.kernels.bpmf_topn import topn_scores_pallas
from repro.kernels.chol_solve import chol_solve_sample_pallas

K = 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_text(one_chip, fn, *shapes, **static) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return fn.lower(*args, **static).compile().as_text()


@pytest.mark.parametrize("n", [5_775, 483_500])
@pytest.mark.parametrize("w", [8, 128, 512])
def test_gather_syrk_compiles_for_v5e(one_chip, w, n):
    rows = 4096
    text = _compile_text(
        one_chip, gather_syrk_pallas,
        ((rows, w), jnp.int32), ((rows, w), jnp.float32),
        ((rows, w), jnp.float32), ((n, LANES), jnp.float32),
        k=K, block_w=min(w, 128),
    )
    assert "tpu_custom_call" in text


def test_gather_syrk_stacked_draws_compiles_for_v5e(one_chip):
    """The fold-in's stacked-draw grid axis, with the bf16 product."""
    text = _compile_text(
        one_chip, gather_syrk_pallas,
        ((64, 32), jnp.int32), ((64, 32), jnp.float32),
        ((64, 32), jnp.float32), ((8, 5_775, LANES), jnp.float32),
        k=K, block_w=32, bf16=True,
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n_items,topk,block_n", [
    (5_775, 10, 128),
    (27_278, 10, 128),
    (5_775, 1024, 1024),   # the seen-item fetch of the heaviest ChEMBL user
])
def test_topn_compiles_for_v5e(one_chip, n_items, topk, block_n):
    n_pad = -(-n_items // block_n) * block_n
    text = _compile_text(
        one_chip, topn_scores_pallas,
        ((64, 2 * K), jnp.float32), ((n_pad, 2 * K), jnp.float32),
        topk=topk, n_valid=n_items, block_n=block_n,
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("b", [
    4096,
    16_384,    # one row chunk of the training sweep (gibbs.chunk_rows at K=64)
    16 * 32,   # a fold-in: 16 draws x 32 cold-start users
])
def test_chol_solve_sample_compiles_for_v5e(one_chip, b):
    text = _compile_text(
        one_chip, jax.jit(chol_solve_sample_pallas),
        ((b, K, K), jnp.float32), ((b, K), jnp.float32), ((b, K), jnp.float32),
    )
    assert "tpu_custom_call" in text


def test_masked_syrk_compiles_for_v5e(one_chip):
    text = _compile_text(
        one_chip, masked_syrk_pallas,
        ((4096, 128, K), jnp.float32), ((4096, 128), jnp.float32),
    )
    assert "tpu_custom_call" in text


def _block_moves(text: str, n_elements: int) -> set[str]:
    """Kinds of the float32 `reshape`s and `copy`s of `n_elements` outside
    fused computations: each moves the whole block through HBM on a TPU (a
    reshape that only reinterprets the layout compiles to a `bitcast`)."""
    fused = set(re.findall(r"fusion\(.*?calls=(%[\w.\-]+)", text))
    found, skip = set(), False
    for line in text.splitlines():
        if line and not line[0].isspace():
            skip = line.split(" ")[0] in fused
            continue
        m = re.search(r"= f32\[([\d,]+)\]\S* (reshape|copy)\(", line)
        if not skip and m and \
                math.prod(map(int, m.group(1).split(","))) == n_elements:
            found.add(m.group(2))
    return found


@pytest.mark.parametrize("w,moves", [(78, {"reshape", "copy"}), (80, set())])
def test_bucket_stats_gather_layout_for_v5e(one_chip, w, moves):
    """The training statistics at an ML-20M user chunk: 8,192 rows gathered
    from the (27,278, K) movie table, every row its own segment. At a width
    that is a multiple of the sublane tile the `(C * w, K)` gather is
    reinterpreted as `(C, w, K)` and both contractions read it as it lies;
    at any other width the compiler relays the whole block out first, which
    is why the balanced ladder sends widths of 8 and more aligned."""
    c, n = 8192, 27_278

    def stats(table, idx, val, msk):
        seg = jnp.arange(c, dtype=jnp.int32)
        bucket = DeviceBucket(w, idx, val, msk, seg, c, seg, True)
        return bucket_stats(table, bucket)

    text = _compile_text(
        one_chip, jax.jit(stats), ((n, K), jnp.float32),
        ((c, w), jnp.int32), ((c, w), jnp.float32), ((c, w), jnp.float32),
    )
    assert _block_moves(text, c * w * K) == moves
