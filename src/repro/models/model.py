"""Top-level model: init / forward / loss / decode for every architecture.

Layers are stacked per repeating group and scanned (lax.scan) with optional
per-group rematerialization; heterogeneous tails (e.g. zamba2's 38 = 6x6+2)
run unrolled after the scan. Decode runs the groups as straight-line code,
slicing each group's caches and params out of the stacks, the packed planes
excepted (the kernel reads them in the store).
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import calibrate as CAL
from repro.dist import constrain as C
from repro.kernels import dispatch
from repro.models import attention as A
from repro.models import layers as L
from repro.models import transformer as T

Array = jax.Array


def _dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _init_group(key, cfg: ModelConfig, pattern) -> dict:
    ks = jax.random.split(key, len(pattern))
    return {"layers": [T.init_layer(k, cfg, s) for k, s in zip(ks, pattern)]}


def _init_stack(key, cfg: ModelConfig, num_layers: Optional[int] = None,
                role: str = "decoder") -> dict:
    pattern, n_groups, n_tail = T.group_layout(cfg, num_layers, role)
    kg, kt = jax.random.split(key)
    groups = jax.vmap(lambda k: _init_group(k, cfg, pattern))(
        jax.random.split(kg, n_groups))
    out = {"groups": groups}
    if n_tail:
        tks = jax.random.split(kt, n_tail)
        out["tail"] = [T.init_layer(tks[i], cfg, pattern[i])
                       for i in range(n_tail)]
    return out


def init_params(key, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 6)
    params: dict[str, Any] = {
        "embed": L.init_embedding(ks[0], cfg.padded_vocab, cfg.d_model),
        "decoder": _init_stack(ks[1], cfg),
        "final_norm": L.init_norm(cfg.d_model, cfg.norm),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.init_linear(ks[2], cfg.d_model,
                                          cfg.padded_vocab, scale=0.02)
    if cfg.family == "hybrid":
        params["shared_attn"] = T.init_shared_attn(ks[3], cfg)
    if cfg.family == "ssm":      # RWKV-6: LayerNorm of the embedding
        params["ln0"] = L.init_norm(cfg.d_model, "layernorm")
    if cfg.family == "encdec":
        params["encoder"] = _init_stack(ks[4], cfg, cfg.encoder_layers,
                                        role="encoder")
        params["enc_norm"] = L.init_norm(cfg.d_model, cfg.norm)
    if cfg.conv_stem:
        cks = jax.random.split(ks[5], len(cfg.conv_stem))
        # dict keys (not a list) so the params walk extends the trail with
        # "s{i}" and the serving path resolves to the per-depth "conv.s{i}"
        # policy role (core/policy.serving_path)
        params["conv_stem"] = {f"s{i}": L.init_conv(cks[i], spec)
                               for i, spec in enumerate(cfg.conv_stem)}
    return params


# ---------------------------------------------------------------------------
# Forward (training / prefill)
# ---------------------------------------------------------------------------

def _run_stack(x: Array, stack: dict, cfg: ModelConfig, *, causal: bool,
               shared: Optional[dict] = None,
               cross_src: Optional[Array] = None,
               remat: bool = True, role: str = "decoder",
               calib: Optional[dict] = None
               ) -> tuple[Array, Array, Optional[dict]]:
    """Run one scanned layer stack. ``calib`` (a ``core.calibrate``
    collection) turns on activation-range observation: a tap is installed
    *inside* each scan body so observed statistics ride the scan carry, and
    the EMA ranges feed the quantizers. Returns (x, aux_loss, observed) —
    observed is None when calibration is off (the bit-exact legacy path).
    """
    pattern = T.group_pattern(cfg, role)
    collect = bool(calib)

    def run_layers(h, aux, gp):
        for i, spec in enumerate(pattern):
            h, a = T.apply_layer(h, gp["layers"][i], cfg, spec,
                                 shared=shared, cross_src=cross_src,
                                 causal=causal)
            aux = aux + a
        return h, aux

    def group_body(carry, gp):
        if not collect:
            h, aux = carry
            h, aux = run_layers(h, aux, gp)
            return (h, aux), None
        h, aux, obs = carry
        with L.calib_tap(calib) as tap:
            h, aux = run_layers(h, aux, gp)
        return (h, aux, CAL.merge(obs, tap.observed)), None

    body = jax.checkpoint(group_body) if remat else group_body
    n_groups = jax.tree_util.tree_leaves(stack["groups"])[0].shape[0]
    aux0 = jnp.zeros((), jnp.float32)
    init = (x, aux0, CAL.unseen_like(calib)) if collect else (x, aux0)
    carry, _ = jax.lax.scan(body, init, stack["groups"],
                            unroll=n_groups if cfg.unroll_loops else 1)
    if collect:
        x, aux, obs = carry
    else:
        (x, aux), obs = carry, None

    def run_tail(h, aux):
        for i, lp in enumerate(stack.get("tail", [])):
            h, a = T.apply_layer(h, lp, cfg, pattern[i % len(pattern)],
                                 shared=shared, cross_src=cross_src,
                                 causal=causal)
            aux = aux + a
        return h, aux

    if collect and stack.get("tail"):
        with L.calib_tap(calib) as tap:
            x, aux = run_tail(x, aux)
        obs = CAL.merge(obs, tap.observed)
    else:
        x, aux = run_tail(x, aux)
    return x, aux, obs


def apply_conv_stem(params: dict, cfg: ModelConfig, raw: Array) -> Array:
    """Run raw frontend input through the conv stem -> token sequence.

    raw: (B, H, W, C) pixels (vision) or (B, frames, 1, mels) features
    (speech), with (H, W) == cfg.frontend_hw. Each layer is a quantized
    conv projection (layers.apply_conv); ReLU between layers (the paper's
    CNN activation — its nonnegative range is exactly the include_zero
    affine encoding the serving kernels assume), none after the last.
    Returns (B, stem_tokens, c_out_last) flattened row-major over (H, W).
    """
    x = raw
    last = len(cfg.conv_stem) - 1
    for i, spec in enumerate(cfg.conv_stem):
        x = L.apply_conv(x, params["conv_stem"][f"s{i}"], cfg, spec,
                         f"conv.s{i}")
        if i < last:
            x = jax.nn.relu(x)
    b, h, w, c = x.shape
    return x.reshape(b, h * w, c)


def encode(params: dict, cfg: ModelConfig, inputs: Array) -> Array:
    """The batch-oriented encode path (no KV cache, whole-sequence waves).

    inputs: raw 4-D (B, H, W, C) frontend input when ``cfg.conv_stem`` is
    set, else pre-embedded (B, T, d_model) stub embeddings (the pre-conv
    behavior). Returns (B, T, d_model) encoder states:

    - encdec: conv stem -> bidirectional encoder stack -> enc_norm (the
      cross-attention source ``forward``/``init_decode_state`` consume);
    - vlm: the conv stem alone — its transformer *is* the cross-attending
      decoder, so the stem output is the image-token sequence.
    """
    dtype = _dtype(cfg)
    if cfg.conv_stem:
        assert inputs.ndim == 4, (
            f"conv_stem set: encode() wants raw (B, H, W, C), got "
            f"{inputs.shape}")
        x = apply_conv_stem(params, cfg, inputs)
    else:
        x = inputs
    x = x.astype(dtype)
    if cfg.family == "encdec":
        enc, _, _ = _run_stack(x, params["encoder"], cfg, causal=False,
                               remat=False, role="encoder")
        return L.apply_norm(enc, params["enc_norm"], cfg.norm)
    return x


def _ln0(x: Array, params: dict) -> Array:
    """RWKV-6's LayerNorm of the embedding, where the params hold it."""
    if "ln0" not in params:
        return x
    return L.apply_norm(x, params["ln0"], "layernorm")


def _embed(params: dict, cfg: ModelConfig, tokens: Array, dtype) -> Array:
    x = C.constrain_batch(L.embed(tokens, params["embed"], dtype))
    if cfg.scale_embed:
        x = x * jnp.asarray(cfg.d_model ** 0.5, dtype)
    return _ln0(x, params)


class ForwardOut(NamedTuple):
    logits: Array
    aux_loss: Array
    # observed activation ranges ({path: [lo, hi]}, core/calibrate.py) when
    # the caller passed a calibration collection; None otherwise
    calib: Optional[dict] = None


def forward(params: dict, cfg: ModelConfig, tokens: Array, *,
            enc_inputs: Optional[Array] = None,
            image_embeds: Optional[Array] = None,
            remat: bool = True, calib: Optional[dict] = None) -> ForwardOut:
    """tokens: (B, T) int32. enc_inputs: (B, S_enc, d) stubbed frontend
    embeddings (encdec). image_embeds: (B, n_img, d) stubbed patch embeddings
    (vlm). ``calib``: EMA activation-range collection (``core.calibrate``) —
    quantizers use its frozen ranges and the ForwardOut reports this pass's
    observed ranges for the EMA update (power-aware QAT, DESIGN.md §9)."""
    dtype = _dtype(cfg)
    collect = bool(calib)
    x = _embed(params, cfg, tokens, dtype)

    obs = CAL.unseen_like(calib) if collect else None
    cross_src = None
    # raw 4-D frontend input runs through the conv stem first (when the
    # config owns one); 3-D input is pre-embedded (the stub path), unchanged
    if cfg.conv_stem and enc_inputs is not None and enc_inputs.ndim == 4:
        enc_inputs = apply_conv_stem(params, cfg, enc_inputs)
    if cfg.conv_stem and image_embeds is not None and image_embeds.ndim == 4:
        image_embeds = apply_conv_stem(params, cfg, image_embeds)
    if cfg.family == "encdec":
        assert enc_inputs is not None
        enc, _, enc_obs = _run_stack(enc_inputs.astype(dtype),
                                     params["encoder"], cfg, causal=False,
                                     remat=remat, role="encoder", calib=calib)
        cross_src = L.apply_norm(enc, params["enc_norm"], cfg.norm)
        if collect:
            obs = CAL.merge(obs, enc_obs)
    elif cfg.family == "vlm":
        assert image_embeds is not None
        cross_src = image_embeds.astype(dtype)

    x, aux, dec_obs = _run_stack(x, params["decoder"], cfg, causal=True,
                                 shared=params.get("shared_attn"),
                                 cross_src=cross_src, remat=remat,
                                 calib=calib)
    if collect:
        obs = CAL.merge(obs, dec_obs)
    x = L.apply_norm(x, params["final_norm"], cfg.norm)

    def head(h):
        if cfg.tie_embeddings:
            return L.unembed(h, params["embed"],
                             L.module_quant(cfg, "lm_head"))
        return L.project(h, params["lm_head"], cfg, "lm_head")

    if collect:
        with L.calib_tap(calib) as tap:
            logits = head(x)
        obs = CAL.merge(obs, tap.observed)
    else:
        logits = head(x)
    logits = L.softcap(logits.astype(jnp.float32), cfg.logit_softcap)
    return ForwardOut(logits=logits, aux_loss=aux, calib=obs)


def lm_loss(params: dict, cfg: ModelConfig, tokens: Array, labels: Array,
            *, enc_inputs=None, image_embeds=None, remat: bool = True,
            aux_weight: float = 0.01, calib: Optional[dict] = None,
            return_calib: bool = False):
    out = forward(params, cfg, tokens, enc_inputs=enc_inputs,
                  image_embeds=image_embeds, remat=remat, calib=calib)
    logits = out.logits
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    loss = loss + aux_weight * out.aux_loss
    if return_calib:
        return loss, out.calib
    return loss


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

class DecodeState(NamedTuple):
    caches: Any            # stacked per-group caches (+ "tail" list)
    cross_kv: Any          # stacked per-group cross (K, V) or None
    position: Array


def init_decode_state(params: dict, cfg: ModelConfig, batch: int,
                      max_len: int, *, enc_inputs=None, image_embeds=None
                      ) -> DecodeState:
    dtype = _dtype(cfg)
    pattern, n_groups, n_tail = T.group_layout(cfg)

    def one_group_cache(_):
        return tuple(T.init_layer_cache(cfg, s, batch, max_len, dtype)
                     for s in pattern)

    caches = {"groups": jax.vmap(one_group_cache)(jnp.arange(n_groups))}
    if n_tail:
        caches["tail"] = [T.init_layer_cache(cfg, pattern[i], batch, max_len,
                                             dtype) for i in range(n_tail)]

    cross_kv = None
    if cfg.family in ("encdec", "vlm"):
        if cfg.conv_stem and enc_inputs is not None and enc_inputs.ndim == 4:
            enc_inputs = apply_conv_stem(params, cfg, enc_inputs)
        if cfg.conv_stem and image_embeds is not None \
                and image_embeds.ndim == 4:
            image_embeds = apply_conv_stem(params, cfg, image_embeds)
        if cfg.family == "encdec":
            assert enc_inputs is not None
            enc, _, _ = _run_stack(enc_inputs.astype(dtype),
                                   params["encoder"], cfg, causal=False,
                                   remat=False, role="encoder")
            src = L.apply_norm(enc, params["enc_norm"], cfg.norm)
        else:
            assert image_embeds is not None
            src = image_embeds.astype(dtype)

        # project cross K/V once per cross-attn layer (stacked over groups)
        def project_group(gp):
            outs = []
            for i, spec in enumerate(pattern):
                if spec.kind == "cross_attn":
                    outs.append(A.project_cross_kv(src,
                                                   gp["layers"][i]["xattn"],
                                                   cfg))
            return tuple(outs)

        cross_kv = jax.vmap(project_group)(params["decoder"]["groups"])
    return DecodeState(caches=caches, cross_kv=cross_kv,
                       position=jnp.zeros((), jnp.int32))


def _take(tree, g: int):
    """Every leaf of ``tree`` at index ``g`` of its leading (group) axis."""
    return jax.tree_util.tree_map(
        lambda a: jax.lax.index_in_dim(a, g, keepdims=False), tree)


def _group_params(groups: dict, g: int) -> dict:
    """Group ``g``'s params out of the stacked groups: every leaf sliced at
    ``g``, except the packed planes of a module that holds one matrix a
    group, (G, P, K/8, N). Those stay in the store as
    ``dispatch.StackedPlanes(stack, g)``, which the packed kernel reads in
    place: a slice of a leaf that feeds a kernel is a copy of it."""
    def one(path, a):
        if getattr(path[-1], "key", None) in dispatch.PLANE_KEYS \
                and a.ndim == 4:
            return dispatch.StackedPlanes(a, g)
        return jax.lax.index_in_dim(a, g, keepdims=False)

    return jax.tree_util.tree_map_with_path(one, groups)


def decode_step(params: dict, cfg: ModelConfig, state: DecodeState,
                tokens: Array) -> tuple[Array, DecodeState]:
    """tokens: (B, 1) -> (logits (B, 1, V), new state)."""
    dtype = _dtype(cfg)
    pattern, _, _ = T.group_layout(cfg)
    shared = params.get("shared_attn")
    x = _embed(params, cfg, tokens, dtype)

    def group_body(h, gp, gcache, gcross):
        new_caches = []
        xi = 0
        for i, spec in enumerate(pattern):
            ckv = None
            if spec.kind == "cross_attn" and gcross is not None:
                ckv = gcross[xi]
                xi += 1
            h, c = T.decode_layer(h, gcache[i], gp["layers"][i], cfg, spec,
                                  shared=shared, cross_kv=ckv)
            new_caches.append(c)
        return h, tuple(new_caches)

    groups = params["decoder"]["groups"]
    n_groups = jax.tree_util.tree_leaves(groups)[0].shape[0]
    # ALWAYS unrolled for decode, a Python loop over the groups: a
    # while-loop over groups makes GSPMD all-gather the stacked KV caches
    # as loop xs (measured 7.1e10 B/device per step — the entire global
    # cache; §Perf iteration 4b). Decode bodies are single-token, so
    # straight-line code is cheap to compile and keeps every layer's cache
    # shard-local.
    outs = []
    for g in range(n_groups):
        gcross = (None if state.cross_kv is None
                  else _take(state.cross_kv, g))
        x, c = group_body(x, _group_params(groups, g),
                          _take(state.caches["groups"], g), gcross)
        outs.append(c)
    new_group_caches = (jax.tree_util.tree_map(lambda *a: jnp.stack(a),
                                               *outs)
                        if outs else state.caches["groups"])

    new_caches = {"groups": new_group_caches}
    if "tail" in state.caches:
        tail_caches = []
        for i, lp in enumerate(params["decoder"].get("tail", [])):
            x, c = T.decode_layer(x, state.caches["tail"][i], lp, cfg,
                                  pattern[i % len(pattern)], shared=shared)
            tail_caches.append(c)
        new_caches["tail"] = tail_caches

    x = L.apply_norm(x, params["final_norm"], cfg.norm)
    if cfg.tie_embeddings:
        logits = L.unembed(x, params["embed"],
                           L.module_quant(cfg, "lm_head"))
    else:
        logits = L.project(x, params["lm_head"], cfg, "lm_head")
    logits = L.softcap(logits.astype(jnp.float32), cfg.logit_softcap)
    return logits, DecodeState(caches=new_caches, cross_kv=state.cross_kv,
                               position=state.position + 1)
