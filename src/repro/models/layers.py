"""Building blocks for the LM architectures.

Sharding philosophy (see DESIGN.md §4): activations are *token-sharded* —
batch over (pod, data), sequence over model — so every architecture balances
perfectly regardless of head counts. Parameters are FSDP-sharded; attention
all-gathers the (small, GQA) KV heads over the model axis; MoE uses an
explicit shard_map dispatch. Collectives that XLA can overlap with compute
are preferred everywhere (the paper's async-communication discipline).
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

Params = dict[str, Any]

# Concrete mesh made visible to layers that open shard_map regions (the EP
# MoE dispatch). jit in/out shardings carry only the abstract mesh, whose
# axes are Auto — shard_map needs the real one.
_ACTIVE_MESH: list = []


@contextlib.contextmanager
def active_mesh(mesh):
    _ACTIVE_MESH.append(mesh)
    try:
        yield
    finally:
        _ACTIVE_MESH.pop()


def get_active_mesh():
    return _ACTIVE_MESH[-1] if _ACTIVE_MESH else None


# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                # 0 -> d_model // n_heads
    # --- MoE ---
    n_experts: int = 0
    n_experts_active: int = 0
    n_experts_pad: int = 0           # allocated experts (0 -> n_experts); pad
    moe_d_ff: int = 0                # so the expert axis divides the TP width
    capacity_factor: float = 1.25
    # --- attention flavour ---
    rope_theta: float = 10_000.0
    sliding_window: int = 0          # 0 = full attention
    local_global_period: int = 0     # gemma2: every 2nd layer global
    attn_softcap: float = 0.0
    logit_softcap: float = 0.0
    attn_scale: float = 0.0          # 0 -> 1/sqrt(head_dim)
    norm_eps: float = 1e-6
    norm_type: str = "rms"           # rms | layer
    post_norms: bool = False         # gemma2 sandwich norms
    tie_embeddings: bool = True
    mlp_act: str = "silu"            # silu | gelu
    mlp_gated: bool = True           # gated (3-matrix) vs classic (2-matrix)
    qkv_bias: bool = False
    embed_scale: bool = False        # gemma2 multiplies embeddings by sqrt(d)
    # --- ssm / hybrid ---
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_headdim: int = 64
    ssm_expand: int = 2
    ssm_groups: int = 1
    attn_every: int = 0              # zamba: shared attn block period
    slstm_every: int = 0             # xlstm: one sLSTM per group of this size
    # --- enc-dec (whisper) ---
    encoder_layers: int = 0
    encoder_seq: int = 0
    # --- vlm ---
    n_patches: int = 0
    mrope_sections: tuple[int, ...] = ()
    # --- dtypes / training ---
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    moment_dtype: Any = jnp.float32
    remat: bool = True
    fsdp_pod: bool = False           # shard params over pod axis too (kimi)
    attn_chunk: int = 1024           # KV block for chunked (flash) attention
    chunked_attn_min_len: int = 8192
    # --- perf-variant knobs (EXPERIMENTS.md §Perf; defaults = baseline) ---
    attn_probs_bf16: bool = False    # store softmax blocks in bf16
    moe_group_dispatch: bool = False # per-sequence dispatch groups (no global sort)
    moe_ep_shard_map: bool = False   # explicit EP dispatch inside shard_map
                                     # (replicated-dispatch + psum combine;
                                     # bypasses GSPMD gather partialization)
    ssm_chunk: int = 128             # SSD / mLSTM chunk length
    ssd_fold_decay: bool = False     # fold exp(cumsum) into B/C, skip decay tensor
    slstm_reshard: bool = False      # reshard seq->replicated around the sLSTM
                                     # time scan (else every step collects the
                                     # sequence-sharded slice = per-step comms)
    remat_policy: str = "nothing"    # nothing | dots (save matmul outputs)

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    def model_flops_per_token(self) -> float:
        """6 * N(active) — the standard training-FLOPs model."""
        return 6.0 * self.active_params()

    def active_params(self) -> float:
        """Parameter count that participates per token (MoE: top-k only)."""
        d, hd = self.d_model, self.hd
        per_layer = d * (self.n_heads + 2 * self.n_kv_heads + 0) * hd  # qkv
        per_layer += self.n_heads * hd * d                              # out
        n_mats = 3 if self.mlp_gated else 2
        if self.is_moe:
            per_layer += n_mats * d * self.moe_d_ff * self.n_experts_active
            per_layer += d * self.n_experts                             # router
        elif self.d_ff:
            per_layer += n_mats * d * self.d_ff
        total = self.n_layers * per_layer
        total += self.vocab_size * d * (1 if self.tie_embeddings else 2)
        return float(total)

    def total_params(self) -> float:
        d = self.d_model
        n_mats = 3 if self.mlp_gated else 2
        per_layer = d * (self.n_heads + 2 * self.n_kv_heads) * self.hd
        per_layer += self.n_heads * self.hd * d
        if self.is_moe:
            per_layer += 3 * d * self.moe_d_ff * self.n_experts + d * self.n_experts
        elif self.d_ff:
            per_layer += n_mats * d * self.d_ff
        total = self.n_layers * per_layer
        total += self.vocab_size * d * (1 if self.tie_embeddings else 2)
        return float(total)


# ---------------------------------------------------------------------------
# Sharding helpers
# ---------------------------------------------------------------------------
BATCH_AXES = ("pod", "data")
SEQ_AXIS = "model"


def logical_batch_spec(batch: int, mesh) -> tuple:
    """Shard batch over as many of (pod, data) as divide it."""
    axes = [a for a in BATCH_AXES if a in mesh.axis_names]
    use = []
    div = 1
    for a in axes:
        if batch % (div * mesh.shape[a]) == 0 and mesh.shape[a] > 1:
            use.append(a)
            div *= mesh.shape[a]
    return tuple(use) if use else (None,)


def constrain(x: jax.Array, spec: P) -> jax.Array:
    """with_sharding_constraint that is a no-op outside a mesh context."""
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, RuntimeError):
        return x


def activation_spec(mesh_axes: tuple[str, ...] = ("pod", "data", "model")) -> P:
    """(B, S, D) activations: batch over (pod,data), seq over model."""
    return P(BATCH_AXES, SEQ_AXIS, None)


# ---------------------------------------------------------------------------
# Initializers / norms
# ---------------------------------------------------------------------------
def dense_init(key, shape, dtype, scale: float | None = None):
    fan_in = shape[0] if len(shape) >= 2 else 1
    std = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (std * jax.random.truncated_normal(key, -2.0, 2.0, shape)).astype(dtype)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float, *, offset: float = 1.0) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (offset + scale.astype(jnp.float32))).astype(dtype)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


def softcap(x: jax.Array, cap: float) -> jax.Array:
    if cap <= 0.0:
        return x
    return jnp.tanh(x / cap) * cap


# ---------------------------------------------------------------------------
# Rotary embeddings (RoPE and M-RoPE)
# ---------------------------------------------------------------------------
def rope_freqs(hd: int, theta: float, dtype=jnp.float32) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=dtype) / hd))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, hd); positions: (B, S) int32."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, hd/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(
    x: jax.Array, positions: jax.Array, theta: float, sections: tuple[int, ...]
) -> jax.Array:
    """Multimodal RoPE (Qwen2-VL): positions (B, 3, S) for (t, h, w) axes.

    The hd/2 frequency lanes are split into `sections` (summing to hd/2); each
    section rotates by its own position channel.
    """
    hd = x.shape[-1]
    assert sum(sections) == hd // 2, (sections, hd)
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    # Build per-lane positions by selecting the section's position channel.
    sec_id = jnp.repeat(
        jnp.arange(len(sections)), jnp.array(sections), total_repeat_length=hd // 2
    )  # static repeat
    pos = positions.astype(jnp.float32)  # (B, 3, S)
    lane_pos = jnp.take(pos, sec_id, axis=1)  # (B, hd/2, S)
    angles = jnp.einsum("bks,k->bsk", lane_pos, freqs)  # (B, S, hd/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------
def init_attention(key, cfg: ModelConfig, d_kv_src: int | None = None) -> Params:
    d, hd = cfg.d_model, cfg.hd
    dsrc = d_kv_src or d
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, cfg.n_heads * hd), cfg.param_dtype),
        "wk": dense_init(ks[1], (dsrc, cfg.n_kv_heads * hd), cfg.param_dtype),
        "wv": dense_init(ks[2], (dsrc, cfg.n_kv_heads * hd), cfg.param_dtype),
        "wo": dense_init(ks[3], (cfg.n_heads * hd, d), cfg.param_dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * hd,), cfg.param_dtype)
        p["bk"] = jnp.zeros((cfg.n_kv_heads * hd,), cfg.param_dtype)
        p["bv"] = jnp.zeros((cfg.n_kv_heads * hd,), cfg.param_dtype)
    return p


def _expand_kv(k: jax.Array, n_heads: int) -> jax.Array:
    """(B, S, Hk, hd) -> (B, S, H, hd) by repeating groups."""
    b, s, hk, hd = k.shape
    rep = n_heads // hk
    return jnp.repeat(k, rep, axis=2) if rep > 1 else k


def attention_scores_mask(
    q_pos: jax.Array, k_pos: jax.Array, *, causal: bool, window
) -> jax.Array:
    """(..., Sq, Sk) boolean mask. q_pos/k_pos are int32 position vectors.

    `window` may be a python int or a traced scalar (per-layer scanned
    metadata, e.g. gemma2's alternating local/global pattern); 0 disables it.
    """
    diff = q_pos[..., :, None] - k_pos[..., None, :]
    mask = jnp.ones(diff.shape, bool)
    if causal:
        mask &= diff >= 0
    if isinstance(window, int):
        if window > 0:
            mask &= diff < window
    else:
        w = jnp.asarray(window, jnp.int32)
        mask &= (w <= 0) | (diff < w)
    return mask


def multi_head_attention(
    q: jax.Array,            # (B, Sq, H, hd)
    k: jax.Array,            # (B, Sk, Hk, hd)
    v: jax.Array,            # (B, Sk, Hk, hd)
    *,
    causal: bool,
    window: int = 0,
    attn_softcap: float = 0.0,
    scale: float = 0.0,
    q_offset: jax.Array | int = 0,
    kv_len: jax.Array | None = None,   # valid KV length (decode)
    chunk: int = 0,                    # 0 = direct; else chunked flash
    probs_bf16: bool = False,
) -> jax.Array:
    """Unified attention. Returns (B, Sq, H, hd).

    Direct path materializes (B, H, Sq, Sk) scores; the chunked path scans
    over KV blocks with an online softmax (jnp flash attention) so long
    prefills never materialize the quadratic score tensor. Both paths accept
    GQA by expanding KV heads.
    """
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    scale = scale or (1.0 / math.sqrt(hd))
    k = _expand_kv(k, h)
    v = _expand_kv(v, h)
    q_pos = q_offset + jnp.arange(sq, dtype=jnp.int32)
    k_pos = jnp.arange(sk, dtype=jnp.int32)

    if chunk and sk > chunk:
        return _chunked_attention(
            q, k, v, scale=scale, causal=causal, window=window,
            attn_softcap=attn_softcap, q_pos=q_pos, k_pos=k_pos,
            kv_len=kv_len, chunk=chunk, probs_bf16=probs_bf16,
        )

    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    scores = softcap(scores, attn_softcap)
    mask = attention_scores_mask(q_pos, k_pos, causal=causal, window=window)
    if kv_len is not None:
        mask &= k_pos[None, :] < kv_len[:, None] if kv_len.ndim else k_pos < kv_len
    scores = jnp.where(mask, scores, -1e30)
    probs_dtype = jnp.bfloat16 if probs_bf16 else q.dtype
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(probs_dtype)
    from jax.ad_checkpoint import checkpoint_name

    probs = checkpoint_name(probs, "attn_probs")
    return jnp.einsum(
        "bhqk,bkhd->bqhd", probs, v.astype(probs_dtype),
        preferred_element_type=jnp.float32,
    ).astype(q.dtype)


def _chunked_attention(
    q, k, v, *, scale, causal, window, attn_softcap, q_pos, k_pos, kv_len, chunk,
    probs_bf16: bool = False,
):
    """Online-softmax attention scanned over KV chunks (jnp flash attention).

    Memory per step is O(B * Sq * H * chunk) instead of O(B * H * Sq * Sk).
    Serves as the CPU-lowerable oracle for the Pallas flash kernel.
    """
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    n_chunks = -(-sk // chunk)
    pad = n_chunks * chunk - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pad), constant_values=jnp.iinfo(jnp.int32).max // 2)
    kc = k.reshape(b, n_chunks, chunk, h, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, chunk, h, hd).transpose(1, 0, 2, 3, 4)
    kp = k_pos.reshape(n_chunks, chunk)

    qf = q.astype(jnp.float32)

    def step(carry, blk):
        acc, m, l = carry
        kb, vb, kpb = blk
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kb.astype(jnp.float32)) * scale
        s = softcap(s, attn_softcap)
        mask = attention_scores_mask(q_pos, kpb, causal=causal, window=window)
        if kv_len is not None:
            mask = mask & (kpb[None, :] < kv_len)
        s = jnp.where(mask, s, -1e30)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1)
        if probs_bf16:
            # the (B,H,Sq,BK) probability block is the traffic hot spot;
            # bf16 halves it (accumulation stays f32 via preferred type)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(jnp.bfloat16), vb.astype(jnp.bfloat16),
                preferred_element_type=jnp.float32,
            )
        else:
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p, vb.astype(jnp.float32)
            )
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((b, h, sq, hd), jnp.float32)
    m0 = jnp.full((b, h, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(step, (acc0, m0, l0), (kc, vc, kp))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def attention_block(
    params: Params,
    x: jax.Array,                   # (B, S, D)
    cfg: ModelConfig,
    *,
    positions: jax.Array,           # (B, S) or (B, 3, S) for M-RoPE
    causal: bool = True,
    window: int = 0,
    kv_src: jax.Array | None = None,   # cross-attention source
    cache: dict | None = None,          # {"k","v","pos"} decode cache
    use_rope: bool = True,
) -> tuple[jax.Array, dict | None]:
    """Projection + RoPE + attention + output projection.

    With `cache`, runs one decode step: writes K/V at cache["pos"] and attends
    over the valid prefix. Returns (out, new_cache).
    """
    b, s, d = x.shape
    h, hk, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    src = kv_src if kv_src is not None else x

    q = (x @ params["wq"].astype(cfg.dtype)).reshape(b, s, h, hd)
    k = (src @ params["wk"].astype(cfg.dtype)).reshape(b, src.shape[1], hk, hd)
    v = (src @ params["wv"].astype(cfg.dtype)).reshape(b, src.shape[1], hk, hd)
    if "bq" in params:
        q = q + params["bq"].astype(cfg.dtype).reshape(h, hd)
        k = k + params["bk"].astype(cfg.dtype).reshape(hk, hd)
        v = v + params["bv"].astype(cfg.dtype).reshape(hk, hd)

    if use_rope and kv_src is None:
        if cfg.mrope_sections:
            q = apply_mrope(q, positions, cfg.rope_theta, cfg.mrope_sections)
            k = apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections)
        else:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)

    new_cache = None
    if cache is not None and kv_src is None and "pos" in cache:
        # Decode: append to sequence-sharded KV cache.
        pos = cache["pos"]
        ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, pos, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, pos, 0, 0))
        ck = constrain(ck, P(BATCH_AXES, SEQ_AXIS, None, None))
        cv = constrain(cv, P(BATCH_AXES, SEQ_AXIS, None, None))
        new_cache = {"k": ck, "v": cv, "pos": pos + s}
        # Causal with q_offset covers both decode (s=1) and prefill (s=S):
        # entries beyond the write position are masked by causality.
        out = multi_head_attention(
            q, ck.astype(cfg.dtype), cv.astype(cfg.dtype),
            causal=True, window=window, attn_softcap=cfg.attn_softcap,
            scale=cfg.attn_scale, q_offset=pos,
        )
    elif cache is not None:
        # Cross-attention with precomputed (static) cache.
        out = multi_head_attention(
            q, cache["k"].astype(cfg.dtype), cache["v"].astype(cfg.dtype),
            causal=False, attn_softcap=cfg.attn_softcap, scale=cfg.attn_scale,
        )
        new_cache = cache
    else:
        # Training / prefill. KV replicated over the model (sequence) axis so
        # the q-sharded chunked scan needs no per-block collectives.
        k = constrain(k, P(BATCH_AXES, None, None, None))
        v = constrain(v, P(BATCH_AXES, None, None, None))
        chunk = cfg.attn_chunk if s >= cfg.chunked_attn_min_len else 0
        out = multi_head_attention(
            q, k, v, causal=causal, window=window,
            attn_softcap=cfg.attn_softcap, scale=cfg.attn_scale, chunk=chunk,
            probs_bf16=cfg.attn_probs_bf16,
        )

    out = out.reshape(b, s, h * hd) @ params["wo"].astype(cfg.dtype)
    return out, new_cache


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------
def init_mlp(key, cfg: ModelConfig, d_ff: int | None = None) -> Params:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {
        "w_up": dense_init(ks[1], (d, f), cfg.param_dtype),
        "w_down": dense_init(ks[2], (f, d), cfg.param_dtype),
    }
    if cfg.mlp_gated:
        p["w_gate"] = dense_init(ks[0], (d, f), cfg.param_dtype)
    return p


def mlp_block(params: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    act = jax.nn.silu if cfg.mlp_act == "silu" else jax.nn.gelu
    u = x @ params["w_up"].astype(cfg.dtype)
    if cfg.mlp_gated:
        g = act(x @ params["w_gate"].astype(cfg.dtype))
        h = g * u
    else:
        h = act(u)
    return h @ params["w_down"].astype(cfg.dtype)


# ---------------------------------------------------------------------------
# Mixture of Experts (scatter dispatch, no one-hot einsum)
# ---------------------------------------------------------------------------
def init_moe(key, cfg: ModelConfig) -> Params:
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    ep = cfg.n_experts_pad or e
    ks = jax.random.split(key, 4)
    return {
        "router": dense_init(ks[0], (d, e), jnp.float32),
        "w_gate": dense_init(ks[1], (ep, d, f), cfg.param_dtype),
        "w_up": dense_init(ks[2], (ep, d, f), cfg.param_dtype),
        "w_down": dense_init(ks[3], (ep, f, d), cfg.param_dtype),
    }


def moe_block(params: Params, x: jax.Array, cfg: ModelConfig) -> tuple[jax.Array, jax.Array]:
    """Top-k capacity-bounded MoE with sort-based scatter dispatch.

    The classic GShard one-hot dispatch einsum costs 2*T*E*C*D flops — at 384
    experts that is ~400x the useful expert compute. We instead sort token
    replicas by expert, compute in-expert positions from cumulative counts,
    and *scatter* into a (E, C, D) buffer: only data movement, no fake flops.
    This is the same static-capacity/padding discipline as the BPMF bucket
    planner (DESIGN.md §5). Expert weights are sharded experts->model; XLA
    partitions the scatter/batched-matmul/gather pipeline.

    Returns (out (B,S,D), aux_loss scalar).
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.n_experts_active
    if cfg.moe_ep_shard_map and s * k >= 4 * e:
        mesh = get_active_mesh()
        if mesh is not None and mesh.shape.get(SEQ_AXIS, 1) > 1:
            return _moe_ep_shard_map(params, x, cfg, mesh)
    if cfg.moe_group_dispatch and s * k >= 4 * e:
        return _moe_grouped(params, x, cfg)
    t = b * s
    xf = x.reshape(t, d)

    logits = (xf.astype(jnp.float32)) @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)                     # (T, k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # Load-balancing auxiliary loss (Switch-style).
    me = probs.mean(0)
    ce = jnp.zeros((e,), jnp.float32).at[top_e.reshape(-1)].add(1.0) / (t * k)
    aux = e * jnp.sum(me * ce)

    flat_e = top_e.reshape(-1)                                 # (T*k,)
    flat_t = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    flat_w = top_w.reshape(-1)

    order = jnp.argsort(flat_e)                                # stable enough
    se, st_, sw = flat_e[order], flat_t[order], flat_w[order]
    counts = jnp.zeros((e,), jnp.int32).at[se].add(1)
    offsets = jnp.cumsum(counts) - counts                      # exclusive
    pos_in_e = jnp.arange(t * k, dtype=jnp.int32) - offsets[se]

    cap = int(math.ceil(t * k / e * cfg.capacity_factor))
    cap = max(cap, 1)
    keep = pos_in_e < cap
    safe_pos = jnp.where(keep, pos_in_e, cap - 1)

    e_pad = cfg.n_experts_pad or e
    buf = jnp.zeros((e_pad, cap, d), cfg.dtype)
    gathered = jnp.where(keep[:, None], xf[st_], 0.0)
    buf = buf.at[se, safe_pos].add(gathered.astype(cfg.dtype))
    buf = constrain(buf, P(SEQ_AXIS, None, None))

    act = jax.nn.silu if cfg.mlp_act == "silu" else jax.nn.gelu
    g = jnp.einsum("ecd,edf->ecf", buf, params["w_gate"].astype(cfg.dtype))
    u = jnp.einsum("ecd,edf->ecf", buf, params["w_up"].astype(cfg.dtype))
    y = jnp.einsum("ecf,efd->ecd", act(g) * u, params["w_down"].astype(cfg.dtype))
    y = constrain(y, P(SEQ_AXIS, None, None))

    back = y[se, safe_pos]                                     # (T*k, D)
    back = jnp.where(keep[:, None], back, 0.0) * sw[:, None].astype(cfg.dtype)
    out = jnp.zeros((t, d), cfg.dtype).at[st_].add(back)
    out = out.reshape(b, s, d)
    return out, aux


def _moe_ep_shard_map(
    params: Params, x: jax.Array, cfg: ModelConfig, mesh
) -> tuple[jax.Array, jax.Array]:
    """Explicit expert parallelism inside shard_map (perf variant round 2).

    GSPMD's auto-partitioner resolves the dispatch gather/scatter with
    partial-result all-reduces (5.4 TB/device/step on kimi-k2 — §Perf).
    Inside shard_map, nothing is second-guessed: tokens are replicated over
    the model axis (one boundary all-gather); each model shard routes *all*
    local tokens but scatters/computes only its own E/P experts, and the
    partial outputs are psum'ed over 'model'. Comm per layer = token
    activations once (gather) + once (reduce) — the replicated-dispatch EP
    scheme. The capacity/sort machinery is the group-local dispatch reused
    on purely local arrays.
    """
    b, s, d = x.shape
    e, kk = cfg.n_experts, cfg.n_experts_active
    e_pad = cfg.n_experts_pad or e
    pm = mesh.shape[SEQ_AXIS]
    assert e_pad % pm == 0, (e_pad, pm)
    e_loc = e_pad // pm
    batch_axes = tuple(a for a in BATCH_AXES if a in mesh.axis_names)
    fsdp_axes = tuple(
        a for a in (("pod", "data") if cfg.fsdp_pod else ("data",))
        if a in mesh.axis_names
    )
    import numpy as _np

    fsdp_size = int(_np.prod([mesh.shape[a] for a in fsdp_axes])) if fsdp_axes else 1
    f = cfg.moe_d_ff
    # expert weights follow param_pspecs: experts->model, largest dim->fsdp
    w_shard_ok = fsdp_size > 1 and d % fsdp_size == 0

    def region(xl, router, wg, wu, wd):
        # xl: (B_loc, S, D) replicated over model; w*: (E_loc, D(/fsdp), F)
        if w_shard_ok:
            wg = jax.lax.all_gather(wg, fsdp_axes, axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, fsdp_axes, axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, fsdp_axes, axis=2, tiled=True)
        m_idx = jax.lax.axis_index(SEQ_AXIS)
        bl = xl.shape[0]

        logits = xl.astype(jnp.float32) @ router                 # (B_loc, S, E)
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_e = jax.lax.top_k(probs, kk)
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

        me = probs.mean((0, 1))
        ce = jnp.zeros((e,), jnp.float32).at[top_e.reshape(-1)].add(1.0) / (bl * s * kk)
        aux = e * jnp.sum(me * ce)
        aux = jax.lax.pmean(aux, batch_axes) if batch_axes else aux

        sk = s * kk
        flat_e = top_e.reshape(bl, sk)
        flat_t = jnp.broadcast_to(
            jnp.repeat(jnp.arange(s, dtype=jnp.int32), kk), (bl, sk)
        )
        flat_w = top_w.reshape(bl, sk)
        order = jnp.argsort(flat_e, axis=1)
        se = jnp.take_along_axis(flat_e, order, 1)
        st_ = jnp.take_along_axis(flat_t, order, 1)
        sw = jnp.take_along_axis(flat_w, order, 1)
        gidx = jnp.arange(bl, dtype=jnp.int32)[:, None]

        counts = jnp.zeros((bl, e), jnp.int32).at[gidx, se].add(1)
        offsets = jnp.cumsum(counts, axis=1) - counts
        pos = jnp.arange(sk, dtype=jnp.int32)[None, :] - jnp.take_along_axis(offsets, se, 1)
        cap = max(1, int(math.ceil(sk / e * cfg.capacity_factor)))
        se_loc = se - m_idx * e_loc
        keep = (pos < cap) & (se_loc >= 0) & (se_loc < e_loc)   # my experts only
        safe_e = jnp.clip(se_loc, 0, e_loc - 1)
        safe_pos = jnp.where(keep, pos, cap - 1)

        tok = jnp.take_along_axis(xl, st_[..., None], 1).astype(cfg.dtype)
        gathered = jnp.where(keep[..., None], tok, jnp.zeros((), cfg.dtype))
        buf = jnp.zeros((bl, e_loc, cap, d), cfg.dtype)
        buf = buf.at[gidx, safe_e, safe_pos].add(gathered)

        act = jax.nn.silu if cfg.mlp_act == "silu" else jax.nn.gelu
        g = jnp.einsum("becd,edf->becf", buf, wg.astype(cfg.dtype))
        u = jnp.einsum("becd,edf->becf", buf, wu.astype(cfg.dtype))
        y = jnp.einsum("becf,efd->becd", act(g) * u, wd.astype(cfg.dtype))

        back = y[gidx, safe_e, safe_pos]
        back = jnp.where(keep[..., None], back, jnp.zeros((), cfg.dtype))
        back = back * sw[..., None].astype(cfg.dtype)
        out = jnp.zeros((bl, s, d), cfg.dtype).at[gidx, st_].add(back)
        out = jax.lax.psum(out, SEQ_AXIS)                        # combine experts
        return out, aux

    bspec = batch_axes if batch_axes else None
    w_spec = P(SEQ_AXIS, fsdp_axes if w_shard_ok else None, None)
    wd_spec = P(SEQ_AXIS, None, fsdp_axes if w_shard_ok else None)
    out, aux = jax.shard_map(
        region,
        mesh=mesh,
        in_specs=(
            P(bspec, None, None),    # tokens replicated over model
            P(None, None),           # router replicated
            w_spec, w_spec, wd_spec,
        ),
        out_specs=(P(bspec, None, None), P()),
        check_vma=False,
    )(x, params["router"], params["w_gate"], params["w_up"], params["w_down"])
    out = constrain(out, P(BATCH_AXES, SEQ_AXIS, None))
    return out, aux


def _moe_grouped(params: Params, x: jax.Array, cfg: ModelConfig) -> tuple[jax.Array, jax.Array]:
    """Per-sequence dispatch groups (perf variant, EXPERIMENTS.md §Perf).

    The global-sort dispatch sorts B*S*k token replicas across the whole
    batch — under GSPMD that drags an all-gather of every token through the
    sort each layer. Grouping by sequence keeps routing, sort, and capacity
    local to each (pod,data) shard (the paper's locality-by-partitioning,
    Sec 4.2): the only cross-shard movement left is the (G, E, C, D) buffer
    resharding to expert-parallel layout — the EP all-to-all.
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.n_experts_active
    e_pad = cfg.n_experts_pad or e

    # Own whole sequences per (pod,data) shard: routing, sort and the
    # capacity scatter then touch only local data. Without this, the scatter
    # reads seq-sharded tokens into a model-sharded buffer and XLA emits
    # full-buffer all-reduces (5.4 TB/device/step on kimi — §Perf).
    x = constrain(x, P(BATCH_AXES, None, None))
    logits = x.astype(jnp.float32) @ params["router"]          # (B, S, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)                     # (B, S, k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    me = probs.mean((0, 1))
    ce = jnp.zeros((e,), jnp.float32).at[top_e.reshape(-1)].add(1.0) / (b * s * k)
    aux = e * jnp.sum(me * ce)

    sk = s * k
    flat_e = top_e.reshape(b, sk)                              # per-group replicas
    flat_t = jnp.broadcast_to(
        jnp.repeat(jnp.arange(s, dtype=jnp.int32), k), (b, sk)
    )
    flat_w = top_w.reshape(b, sk)

    order = jnp.argsort(flat_e, axis=1)                        # group-local sort
    se = jnp.take_along_axis(flat_e, order, 1)
    st_ = jnp.take_along_axis(flat_t, order, 1)
    sw = jnp.take_along_axis(flat_w, order, 1)
    gidx = jnp.arange(b, dtype=jnp.int32)[:, None]

    counts = jnp.zeros((b, e), jnp.int32).at[gidx, se].add(1)
    offsets = jnp.cumsum(counts, axis=1) - counts
    pos = jnp.arange(sk, dtype=jnp.int32)[None, :] - jnp.take_along_axis(offsets, se, 1)
    cap = max(1, int(math.ceil(sk / e * cfg.capacity_factor)))
    keep = pos < cap
    safe_pos = jnp.where(keep, pos, cap - 1)

    tok = jnp.take_along_axis(x, st_[..., None], 1, mode="clip").astype(cfg.dtype)
    zero = jnp.zeros((), cfg.dtype)                            # keep bf16 —
    gathered = jnp.where(keep[..., None], tok, zero)           # 0.0 promotes f32
    gathered = constrain(gathered, P(BATCH_AXES, None, None))  # D stays whole
    buf = jnp.zeros((b, e_pad, cap, d), cfg.dtype)
    buf = buf.at[gidx, se, safe_pos].add(gathered)
    buf = constrain(buf, P(BATCH_AXES, SEQ_AXIS, None, None))  # EP all-to-all

    act = jax.nn.silu if cfg.mlp_act == "silu" else jax.nn.gelu
    g = jnp.einsum("becd,edf->becf", buf, params["w_gate"].astype(cfg.dtype))
    u = jnp.einsum("becd,edf->becf", buf, params["w_up"].astype(cfg.dtype))
    y = jnp.einsum("becf,efd->becd", act(g) * u, params["w_down"].astype(cfg.dtype))
    y = constrain(y, P(BATCH_AXES, SEQ_AXIS, None, None))

    y = constrain(y, P(BATCH_AXES, None, None, None))          # combine a2a back
    back = y.at[gidx, se, safe_pos].get(mode="clip")           # (B, sk, D)
    zero = jnp.zeros((), cfg.dtype)
    back = jnp.where(keep[..., None], back, zero) * sw[..., None].astype(cfg.dtype)
    back = constrain(back, P(BATCH_AXES, None, None))
    out = jnp.zeros((b, s, d), cfg.dtype).at[gidx, st_].add(back)
    out = constrain(out, P(BATCH_AXES, SEQ_AXIS, None))
    return out, aux
