"""EncodeEngine: batch-oriented encoder serving — PANN beyond the LM decoder.

The decode engine (``serve_engine.engine``) is token-oriented: lanes, KV
caches, one step per token. Encoder workloads (vision towers, speech
frontends) are ITEM-oriented: one whole-sequence forward per image or
utterance, no cache, and a per-ITEM power budget instead of per-token.
This engine serves them through the SAME machinery:

  * the same ladder (``serve_engine.ladder``) — rungs planned against the
    per-item ``costs.encoder_cost_profile``, whose conv rows carry the
    exact kh·kw·Cin·Cout·Ho·Wo MAC account, so a layerwise rung trades
    conv-stem bits against encoder attention/mlp bits under one budget;
  * the same one-weight-store materialization (``models/serving.py``):
    every rung a zero-copy view, same avals, ONE jitted encode step for
    the whole ladder — ``warmup``/``assert_no_recompile`` prove it exactly
    as the decode engine does;
  * the same request-side dial: ``power_budget_bits`` / ``min_score``
    resolve through ``select_rung``, and every response carries an
    ``EnergyLedger`` itemizing its rung's per-module bit-flips (the
    ``conv.s{i}`` roles included).

Waves are whole-sequence: requests resolve to rungs, group into
``max_batch`` batches per rung, and each batch is one jitted call on the
rung's view — rung switching between waves is a pointer swap, never a
retrace.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import costs
from repro.core import policy as pol
from repro.core import power as pw
from repro.kernels import dispatch
from repro.models import model as MD
from repro.models import serving
from repro.serve_engine.ladder import build_ladder, select_rung


@dataclasses.dataclass(frozen=True)
class EncodeRequest:
    """One item to encode. ``item`` is the RAW frontend input — (H, W, C)
    pixels / (frames, 1, mels) features when the config owns a conv stem,
    or pre-embedded (T, d_model) stub embeddings when it doesn't. The
    budget/floor fields mean what they mean on a decode ``Request``, but
    per ITEM: the rung whose per-item encode power fits the budget."""
    uid: int
    item: np.ndarray
    power_budget_bits: Optional[int] = None
    min_score: Optional[float] = None


@dataclasses.dataclass
class EncodeResponse:
    uid: int
    encoded: np.ndarray          # (T, d_model) encoder states
    rung_bits: int
    metadata: dict


class EncodeEngine:
    """Multi-operating-point encoder serving runtime (module docstring)."""

    def __init__(self, cfg: ModelConfig, params: Any = None,
                 ladder_bits: Sequence[int] = (2, 3, 4, 6),
                 max_batch: int = 4, mesh=None, par=None,
                 mse_dim: Optional[float] = None,
                 allocation: str = "uniform",
                 backend: str = "packed",
                 weight_store: Optional[serving.WeightStore] = None):
        if (params is None) == (weight_store is None):
            raise ValueError(
                "pass exactly one of params (quantize here) or "
                "weight_store (serve a prebuilt/loaded artifact)")
        dispatch.parse_backend(backend)          # fail fast on typos
        self.backend = backend
        cfg = dataclasses.replace(cfg, kernel_backend=backend)
        self.cfg = cfg
        self.max_batch = int(max_batch)
        self.allocation = allocation
        # per-ITEM profile: conv rows exact, encdec encoder rows at
        # encoder_layers x n_tokens instances — the allocator and the
        # per-response breakdown both price in items, not tokens
        self.profile = costs.encoder_cost_profile(cfg)
        if not self.profile:
            raise ValueError(
                f"{cfg.name} ({cfg.family}) has no encode path: needs a "
                "conv_stem, encoder layers, or image tokens")
        self._macs_item = costs.encoder_macs_per_item(cfg)
        self.ladder = build_ladder(ladder_bits,
                                   d=float(mse_dim or cfg.d_model),
                                   allocation=allocation,
                                   profile=self.profile)
        self.rungs = {op.bits: op for op in self.ladder}
        rung_specs = {op.bits: (op.tree if op.tree is not None
                                else (op.r, op.b_x_tilde))
                      for op in self.ladder}
        if weight_store is not None:
            missing = [b for b in rung_specs if b not in weight_store.views]
            if missing:
                raise ValueError(
                    f"weight_store has no view for rung(s) {missing}; "
                    f"available: {sorted(weight_store.views)}")
            ws = serving.device_put_weight_store(
                serving.WeightStore(
                    store=weight_store.store,
                    views={b: weight_store.views[b] for b in rung_specs}),
                mesh=mesh, par=par)
        else:
            ws = serving.build_weight_store(
                params, cfg, rung_specs, mesh=mesh, par=par,
                pack_planes=dispatch.reads_planes(backend))
        self.weight_store = ws.store
        self.variants = ws.views
        # ONE jitted whole-sequence encode — every rung's view shares its
        # avals, so the ladder shares this single compilation
        self._step = jax.jit(lambda v, x: MD.encode(v, cfg, x))
        self.compilations_after_warmup: Optional[int] = None
        self.items_by_rung = {op.bits: 0 for op in self.ladder}
        self.rung_switches = 0
        self._last_bits: Optional[int] = None

    # -- shapes -------------------------------------------------------------

    def item_shape(self) -> tuple:
        """The per-item input shape this engine encodes."""
        cfg = self.cfg
        if cfg.conv_stem:
            h, w = cfg.frontend_hw
            return (h, w, cfg.conv_stem[0].c_in)
        return (costs.encoder_tokens(cfg), cfg.d_model)

    def _batch(self, items: Sequence[np.ndarray]) -> jnp.ndarray:
        want = self.item_shape()
        rows = []
        for it in items:
            a = np.asarray(it, np.float32)
            if a.shape != want:
                raise ValueError(
                    f"item shape {a.shape} != engine item shape {want}")
            rows.append(a)
        # pad the batch dim to max_batch (repeating row 0) so every wave
        # presents identical avals to the jitted step
        while len(rows) < self.max_batch:
            rows.append(rows[0])
        return jnp.asarray(np.stack(rows))

    # -- jit bookkeeping (same protocol as the decode engine) ---------------

    def _jit_cache_size(self) -> int:
        try:
            return int(self._step._cache_size())
        except Exception:
            return -1

    def warmup(self) -> None:
        """One encode per rung so the single expected compilation happens
        before traffic."""
        x = jnp.zeros((self.max_batch,) + self.item_shape(), jnp.float32)
        for op in self.ladder:
            jax.block_until_ready(self._step(self.variants[op.bits], x))
        self.compilations_after_warmup = self._jit_cache_size()

    def assert_no_recompile(self) -> None:
        if self.compilations_after_warmup is None:
            raise RuntimeError("call warmup() first")
        now = self._jit_cache_size()
        if now < 0 or self.compilations_after_warmup < 0:
            raise RuntimeError(
                "cannot verify the no-recompilation claim: jit cache "
                "introspection (_cache_size) is unavailable on this jax")
        if now > self.compilations_after_warmup:
            raise AssertionError(
                f"encode step recompiled while serving: "
                f"{self.compilations_after_warmup} -> {now} cache entries")

    # -- energy accounting --------------------------------------------------

    def _rung_tree(self, rung) -> pol.PolicyTree:
        if rung.tree is not None:
            return rung.tree
        return pol.uniform_policy(pol.ModuleQuant(
            mode="pann", r=rung.r, b_x_tilde=rung.b_x_tilde))

    def ledger_for(self, rung) -> pw.EnergyLedger:
        """Per-ITEM energy ledger: the per-module breakdown (conv roles
        included) prices this engine's per-item profile under the rung's
        tree; act MACs are the encoder's bidirectional T² attention. The
        'per_token' unit in the ledger's field names reads 'per item'
        here — one charge() per encoded image/utterance."""
        total, breakdown = pol.tree_power_per_token(
            self.profile, self._rung_tree(rung),
            act_macs=self._macs_item.act_macs)
        if rung.tree is None:
            # uniform rung: headline number from the closed-form account,
            # same convention as the decode engine's fp-cache headline
            total = pw.pann_token_bitflips(self._macs_item, rung.r,
                                           rung.b_x_tilde)
        return pw.EnergyLedger(total, breakdown_per_token=breakdown)

    def item_flips(self, bits: int) -> float:
        """Estimated bit flips of encoding ONE item at rung ``bits``."""
        return self.ledger_for(self.rungs[bits]).bitflips_per_token

    # -- serving ------------------------------------------------------------

    def _encode_wave(self, rung, reqs: Sequence[EncodeRequest]
                     ) -> list[EncodeResponse]:
        if self._last_bits is not None and rung.bits != self._last_bits:
            self.rung_switches += 1
        self._last_bits = rung.bits
        self.items_by_rung[rung.bits] += len(reqs)
        x = self._batch([r.item for r in reqs])
        out = np.asarray(self._step(self.variants[rung.bits], x))
        responses = []
        for i, req in enumerate(reqs):
            ledger = self.ledger_for(rung)
            ledger.charge(1)
            meta = {
                "rung_bits": rung.bits,
                "b_x_tilde": rung.b_x_tilde,
                "r": rung.r,
                "allocation": rung.allocation,
                "power_per_weight_mac": rung.power,
                **ledger.report(),
            }
            responses.append(EncodeResponse(uid=req.uid, encoded=out[i],
                                            rung_bits=rung.bits,
                                            metadata=meta))
        return responses

    def encode(self, requests: Sequence[EncodeRequest]
               ) -> list[EncodeResponse]:
        """Serve a batch of mixed-budget encode requests.

        Requests resolve to rungs up front (any infeasible budget/floor
        pair fails the whole call before any work), then group into
        per-rung waves of ``max_batch`` whole-sequence forwards.
        """
        resolved = [select_rung(self.ladder, r.power_budget_bits,
                                r.min_score) for r in requests]
        by_rung: dict[int, list[EncodeRequest]] = {}
        for req, rung in zip(requests, resolved):
            by_rung.setdefault(rung.bits, []).append(req)
        responses: list[EncodeResponse] = []
        for bits in sorted(by_rung):
            reqs = by_rung[bits]
            for i in range(0, len(reqs), self.max_batch):
                responses.extend(
                    self._encode_wave(self.rungs[bits],
                                      reqs[i:i + self.max_batch]))
        return sorted(responses, key=lambda r: r.uid)

    # -- reporting ----------------------------------------------------------

    def describe(self) -> dict:
        total_macs = sum(m.macs for m in self.profile)
        return {
            "workload": "encode",
            "allocation": self.allocation,
            "backend": self.backend,
            "item_shape": list(self.item_shape()),
            "encoder_tokens": costs.encoder_tokens(self.cfg),
            "ladder": [{"bits": op.bits, "b_x_tilde": op.b_x_tilde,
                        "r": round(op.r, 3),
                        "power_per_weight_mac": round(op.power, 2),
                        "total_gbitflips_per_item":
                            round(pw.giga(op.power * total_macs), 3)}
                       for op in self.ladder],
            "max_batch": self.max_batch,
            "compilations_after_warmup": self.compilations_after_warmup,
            "items_by_rung": dict(self.items_by_rung),
            "rung_switches": self.rung_switches,
        }
