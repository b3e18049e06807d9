"""Model assembly: every assigned architecture is a repeating *group pattern*
of layer kinds, stacked and scanned with lax.scan (fast compiles at 100
layers) with optional per-group activation rematerialization.

Layer kinds:
  attn        — self-attention (+MLP) block; window=None means global
  attn_moe    — self-attention + MoE block
  mamba       — Mamba2 (SSD) block
  mamba_attn  — Mamba2 block followed by the zamba2 *shared* attn+MLP block
  rwkv        — RWKV-6 "Finch" time-mix + channel-mix block
  cross_attn  — VLM / enc-dec cross-attention (+MLP) block
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as A
from repro.models import layers as L
from repro.models import mlp as M
from repro.models import rwkv as R
from repro.models import ssm as S

Array = jax.Array


# ---------------------------------------------------------------------------
# Group patterns
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LayerSpec:
    kind: str
    window: Optional[int] = None


def group_pattern(cfg: ModelConfig, role: str = "decoder") -> list[LayerSpec]:
    """Smallest repeating pattern of layers for this architecture."""
    if role == "encoder":  # encdec encoder: bidirectional self-attn blocks
        return [LayerSpec("attn")]
    if cfg.family == "encdec":  # decoder: self-attn + cross-attn every layer
        return [LayerSpec("cross_attn")]
    if cfg.family == "moe":
        return [LayerSpec("attn_moe", cfg.sliding_window)]
    if cfg.family == "ssm":
        return [LayerSpec("rwkv")]
    if cfg.family == "hybrid":
        period = cfg.attn_period or 6
        return [LayerSpec("mamba")] * (period - 1) + [LayerSpec("mamba_attn")]
    if cfg.family == "vlm":
        period = cfg.cross_attn_period or 5
        return [LayerSpec("cross_attn")] + [LayerSpec("attn")] * (period - 1)
    if cfg.local_global_period:  # gemma2: alternate local / global
        return [LayerSpec("attn", cfg.local_window), LayerSpec("attn", None)]
    return [LayerSpec("attn", cfg.sliding_window)]


def group_layout(cfg: ModelConfig, num_layers: Optional[int] = None,
                 role: str = "decoder") -> tuple[list[LayerSpec], int, int]:
    """(pattern, n_groups, n_tail): n_tail layers don't fill a full group and
    run outside the scan (e.g. zamba2's 38 = 6*6 + 2)."""
    pattern = group_pattern(cfg, role)
    n_layers = num_layers if num_layers is not None else cfg.num_layers
    n_groups = n_layers // len(pattern)
    n_tail = n_layers - n_groups * len(pattern)
    return pattern, n_groups, n_tail


# ---------------------------------------------------------------------------
# Per-layer init / apply
# ---------------------------------------------------------------------------

def init_layer(key, cfg: ModelConfig, spec: LayerSpec) -> dict:
    ks = jax.random.split(key, 6)
    d = cfg.d_model
    p: dict[str, Any] = {"norm1": L.init_norm(d, cfg.norm)}
    if spec.kind in ("attn", "attn_moe", "cross_attn"):
        p["attn"] = A.init_attention(ks[0], cfg)
        p["norm2"] = L.init_norm(d, cfg.norm)
        if spec.kind == "attn_moe":
            p["moe"] = M.init_moe(ks[1], cfg)
        else:
            p["mlp"] = M.init_mlp(ks[1], cfg)
        if spec.kind == "cross_attn":
            p["xattn"] = A.init_attention(ks[2], cfg, cross=True)
            p["norm_x"] = L.init_norm(d, cfg.norm)
            p["xgate"] = jnp.zeros((), jnp.float32)
        if cfg.post_norm:
            p["post1"] = L.init_norm(d, cfg.norm)
            p["post2"] = L.init_norm(d, cfg.norm)
    elif spec.kind in ("mamba", "mamba_attn"):
        p["ssm"] = S.init_ssm(ks[0], cfg)
    elif spec.kind == "rwkv":
        p["tm"] = R.init_rwkv_time_mix(ks[0], cfg)
        p["norm2"] = L.init_norm(d, cfg.norm)
        p["cm"] = R.init_rwkv_channel_mix(ks[1], cfg)
    else:
        raise ValueError(spec.kind)
    return p


def init_shared_attn(key, cfg: ModelConfig) -> dict:
    """zamba2: one attention+MLP block shared by all mamba_attn positions."""
    ks = jax.random.split(key, 2)
    return {"norm1": L.init_norm(cfg.d_model, cfg.norm),
            "attn": A.init_attention(ks[0], cfg),
            "norm2": L.init_norm(cfg.d_model, cfg.norm),
            "mlp": M.init_mlp(ks[1], cfg)}


def _residual(x, delta, p, cfg, post_key):
    if cfg.post_norm and post_key in p:
        delta = L.apply_norm(delta, p[post_key], cfg.norm)
    return x + delta


def _apply_moe_dispatch(h, p, cfg: ModelConfig):
    """Baseline dense scan, or the capacity-dispatch path (§Perf) when a
    mesh is active and the config opts in."""
    if cfg.moe_impl == "capacity":
        from repro.dist.constrain import _context_mesh
        from repro.dist.moe_ep import apply_moe_capacity
        mesh = _context_mesh()
        if mesh is not None and hasattr(mesh, "devices"):
            return apply_moe_capacity(h, p, cfg, mesh)
    return M.apply_moe(h, p, cfg)


def apply_layer(x: Array, p: dict, cfg: ModelConfig, spec: LayerSpec, *,
                shared: Optional[dict] = None,
                cross_src: Optional[Array] = None,
                causal: bool = True) -> tuple[Array, Array]:
    """Training/prefill forward of one layer. Returns (x, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    if spec.kind in ("attn", "attn_moe", "cross_attn"):
        h = L.apply_norm(x, p["norm1"], cfg.norm)
        h = A.attend(h, p["attn"], cfg, window=spec.window, causal=causal)
        x = _residual(x, h, p, cfg, "post1")
        if spec.kind == "cross_attn" and cross_src is not None:
            h = L.apply_norm(x, p["norm_x"], cfg.norm)
            h = A.attend(h, p["xattn"], cfg, kv_src=cross_src, causal=False)
            x = x + jnp.tanh(p["xgate"]).astype(x.dtype) * h
        h = L.apply_norm(x, p["norm2"], cfg.norm)
        if spec.kind == "attn_moe":
            h, aux = _apply_moe_dispatch(h, p["moe"], cfg)
        else:
            h = M.apply_mlp(h, p["mlp"], cfg)
        x = _residual(x, h, p, cfg, "post2")
    elif spec.kind in ("mamba", "mamba_attn"):
        h = L.apply_norm(x, p["norm1"], cfg.norm)
        x = x + S.apply_ssm(h, p["ssm"], cfg)
        if spec.kind == "mamba_attn":
            assert shared is not None
            h = L.apply_norm(x, shared["norm1"], cfg.norm)
            x = x + A.attend(h, shared["attn"], cfg, causal=causal)
            h = L.apply_norm(x, shared["norm2"], cfg.norm)
            x = x + M.apply_mlp(h, shared["mlp"], cfg)
    elif spec.kind == "rwkv":
        h = L.apply_norm(x, p["norm1"], cfg.norm)
        y, _, _ = R.apply_time_mix(h, p["tm"], cfg)
        x = x + y
        h = L.apply_norm(x, p["norm2"], cfg.norm)
        y, _ = R.apply_channel_mix(h, p["cm"], cfg)
        x = x + y
    else:
        raise ValueError(spec.kind)
    return x, aux


# ---------------------------------------------------------------------------
# Decode-path per-layer state
# ---------------------------------------------------------------------------

def init_layer_cache(cfg: ModelConfig, spec: LayerSpec, batch: int,
                     max_len: int, dtype) -> Any:
    if spec.kind in ("attn", "attn_moe", "cross_attn"):
        win = spec.window
        cache_len = min(max_len, win) if win else max_len
        return A.init_cache(cfg, batch, cache_len, dtype)
    if spec.kind in ("mamba", "mamba_attn"):
        ssm = S.init_ssm_state(cfg, batch, dtype)
        if spec.kind == "mamba_attn":
            return (ssm, A.init_cache(cfg, batch, max_len, dtype))
        return ssm
    if spec.kind == "rwkv":
        return R.init_rwkv_state(cfg, batch, dtype)
    raise ValueError(spec.kind)


def decode_layer(x: Array, cache: Any, p: dict, cfg: ModelConfig,
                 spec: LayerSpec, *, shared: Optional[dict] = None,
                 cross_kv: Optional[tuple] = None) -> tuple[Array, Any]:
    """Single-token decode step of one layer."""
    if spec.kind in ("attn", "attn_moe", "cross_attn"):
        h = L.apply_norm(x, p["norm1"], cfg.norm)
        h, cache = A.decode_attend(h, cache, p["attn"], cfg,
                                   window=spec.window)
        x = _residual(x, h, p, cfg, "post1")
        if spec.kind == "cross_attn" and cross_kv is not None:
            h = L.apply_norm(x, p["norm_x"], cfg.norm)
            h = A.cross_attend_cached(h, cross_kv, p["xattn"], cfg)
            x = x + jnp.tanh(p["xgate"]).astype(x.dtype) * h
        h = L.apply_norm(x, p["norm2"], cfg.norm)
        if spec.kind == "attn_moe":
            h, _ = M.apply_moe(h, p["moe"], cfg)
        else:
            h = M.apply_mlp(h, p["mlp"], cfg)
        x = _residual(x, h, p, cfg, "post2")
        return x, cache
    if spec.kind in ("mamba", "mamba_attn"):
        if spec.kind == "mamba_attn":
            ssm_state, kv = cache
        else:
            ssm_state, kv = cache, None
        h = L.apply_norm(x, p["norm1"], cfg.norm)
        y, ssm_state = S.decode_ssm(h, ssm_state, p["ssm"], cfg)
        x = x + y
        if spec.kind == "mamba_attn":
            assert shared is not None
            h = L.apply_norm(x, shared["norm1"], cfg.norm)
            y, kv = A.decode_attend(h, kv, shared["attn"], cfg)
            x = x + y
            h = L.apply_norm(x, shared["norm2"], cfg.norm)
            x = x + M.apply_mlp(h, shared["mlp"], cfg)
            return x, (ssm_state, kv)
        return x, ssm_state
    if spec.kind == "rwkv":
        h_tm = L.apply_norm(x, p["norm1"], cfg.norm)[:, 0]
        y, wkv = R.decode_time_mix(h_tm, p["tm"], cfg, cache)
        x = x + y[:, None]
        h_cm = L.apply_norm(x, p["norm2"], cfg.norm)[:, 0]
        y = R.channel_mix(h_cm, cache.shift_cm.astype(h_cm.dtype), p["cm"],
                          cfg)
        x = x + y[:, None]
        new = R.RWKVState(wkv=wkv, shift_tm=h_tm.astype(cache.shift_tm.dtype),
                          shift_cm=h_cm.astype(cache.shift_cm.dtype),
                          length=cache.length + 1)
        return x, new
    raise ValueError(spec.kind)
