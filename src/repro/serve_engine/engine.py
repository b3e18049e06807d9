"""ServeEngine: one checkpoint, a ladder of PANN operating points, per-request
power-accuracy selection — with no re-quantization and no recompilation after
warmup.

Why switching is free (DESIGN.md §6): every rung's view of the one weight
store (``models/serving.build_weight_store``) has the same pytree structure,
shapes, and dtypes (int8 codes + f32 scales); jax.jit keys its compilation
cache on exactly those avals, so ONE traced decode step serves every rung
and moving between rungs is a pointer swap between views. ``warmup()`` runs
each rung once and records the jit cache size; ``assert_no_recompile()``
proves the claim after serving mixed traffic.

The engine interleaves *lanes* (one per in-flight wave) round-robin, one
decode step each — so a 2-bit lane and a 6-bit lane genuinely alternate
operating points between decode steps of a single process.

The serving calls are always instrumented: host spans
(``jax.profiler.TraceAnnotation``, named ``serve.*`` and carrying the wave's
``seq`` and rung) land on the device trace's clock whenever a profiler runs,
and cost about a microsecond when none does. The device side names the
jitted step ``decode_step`` (module ``jit_decode_step``) and scopes inside it
(``ssm_state``, ``act_range``, ``rwkv_shift``, ``rwkv_wkv``). The counters
``decode_steps``, ``prefill_steps`` and ``live_rows`` are reported by
``describe()``, with ``recurrent_state_bytes``, the recurrent state one lane
holds, and ``plane_bytes_in_place`` / ``plane_bytes_copied``, the packed
planes one decode step reads from the weight store where they lie and
through a sliced or padded copy (None until the step is traced).
``kv_blocks_read`` / ``kv_blocks_cached`` sum, over decode steps and
attention calls, the 128-position blocks of the quantized KV cache that
the attention kernel reads (those holding a cached position) and the
blocks the cache holds; None for a model without a quantized cache and
before the first decode step.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import costs
from repro.core import policy as pol
from repro.core import power as pw
from repro.kernels import dispatch
from repro.models import model as MD
from repro.models import rwkv as R
from repro.models import serving
from repro.models import ssm as S
from repro.serve_engine.ladder import build_ladder, select_rung
from repro.serve_engine.scheduler import Request, Response, Scheduler, Wave


@dataclasses.dataclass
class Lane:
    """One in-flight wave: its decode state and the tokens grown so far.

    Public because the fleet (``serve_engine.fleet``) moves lanes BETWEEN
    engines: a prefill host builds the lane, a decode host advances it, and
    a restarted or switched-to host rebuilds it from ``prefix_rows()`` —
    the decode state is re-derivable from the token prefix (teacher-forced
    replay, DESIGN.md §6), so a lane's identity is its tokens, not its
    arrays. ``done`` counts tokens generated before this lane's state was
    (re)built; ``generated`` holds only the tokens grown since."""
    wave: Wave
    state: Any
    tok: Any                 # (max_batch, 1) int32 — last sampled token
    generated: list          # [(max_batch, 1), ...] greedy tokens
    steps_left: int
    done: int = 0            # tokens generated before the latest (re)build

    def generated_rows(self) -> np.ndarray:
        """(n_requests, n_generated_since_build) int32 token matrix —
        what the fleet appends to its per-request records when this lane
        finishes, switches rung, or dies with its host."""
        n = len(self.wave.requests)
        if not self.generated:
            return np.zeros((n, 0), np.int32)
        return np.asarray(jnp.concatenate(self.generated, axis=1))[:n]


def _span(name: str, wave: Wave):
    """A ``serve.*`` host span of ``wave`` in the profiler's trace."""
    return jax.profiler.TraceAnnotation(name, wave=wave.seq,
                                        rung=wave.rung.bits)


class ServeEngine:
    """Multi-operating-point PANN serving runtime (see module docstring)."""

    def __init__(self, cfg: ModelConfig, params: Any = None,
                 ladder_bits: Sequence[int] = (2, 3, 4, 6),
                 max_batch: int = 4, max_len: int = 64, mesh=None,
                 par=None, mse_dim: Optional[float] = None,
                 allocation: str = "uniform",
                 backend: str = "packed",
                 autotune: bool = False,
                 cache_bits: Any = None,
                 weight_store: Optional[serving.WeightStore] = None,
                 frontend_kwargs_fn: Optional[Callable[[int], dict]] = None):
        if (params is None) == (weight_store is None):
            raise ValueError(
                "pass exactly one of params (quantize here) or "
                "weight_store (serve a prebuilt/loaded artifact)")
        if cfg.family in ("encdec", "vlm") and frontend_kwargs_fn is None:
            raise ValueError(
                f"{cfg.family} decode needs a frontend; pass "
                "frontend_kwargs_fn(batch) -> init_decode_state kwargs")
        # quantized KV cache (docs/kv_cache.md): None leaves the fp cache;
        # an int pins every rung's cache width; "auto" lets each rung pick —
        # a uniform rung caches at its own b~x, a layerwise rung lets the
        # allocator trade cache bits against weight bits under one budget
        # (cache pseudo-modules appended to its profile). Trace-time static
        # on the config like the backend: the cache STRUCTURE is fixed,
        # per-rung widths ride in the variants as data (k_nlvl / v_nlvl),
        # so one compiled decode step still serves the whole ladder.
        if cache_bits is not None and cache_bits != "auto":
            cache_bits = int(cache_bits)
            if not 2 <= cache_bits <= 7:
                raise ValueError(
                    f"cache_bits must be in [2, 7] (codes are <= 7 planes), "
                    f"got {cache_bits}")
        self.cache_bits = cache_bits
        if cache_bits is not None:
            cfg = dataclasses.replace(
                cfg, cache_bits=7 if cache_bits == "auto" else cache_bits)
        # the serving-matmul backend (repro.kernels.dispatch) is trace-time
        # static on the config: ONE jitted decode step per backend, still
        # one per ENGINE — every rung of this ladder shares it. The default
        # 'packed' is the platform's own choice: the Pallas kernel on TPU,
        # 'ref' elsewhere (dispatch.resolve_backend)
        dispatch.parse_backend(backend)          # fail fast on typos
        self.backend = backend
        cfg = dataclasses.replace(cfg, kernel_backend=backend)
        self.cfg = cfg
        self.max_batch = int(max_batch)
        self.max_len = int(max_len)
        self.allocation = allocation
        # the per-module MAC profile: feeds the layerwise allocator AND the
        # per-module energy breakdown on every response (either allocation)
        self.profile = costs.module_cost_profile(cfg)
        # "auto" + layerwise: the allocator sees the cache roles as
        # pseudo-modules and spends ONE budget across weights AND cache
        alloc_profile = self.profile
        if cache_bits == "auto" and allocation == "layerwise":
            alloc_profile = self.profile + costs.cache_cost_modules(cfg)
        self.ladder = build_ladder(ladder_bits,
                                   d=float(mse_dim or cfg.d_model),
                                   allocation=allocation,
                                   profile=alloc_profile)
        self.rungs = {op.bits: op for op in self.ladder}
        # per-rung cache width handed to the weight store: an int pins the
        # rung's k_nlvl/v_nlvl leaves; None defers to the rung's PolicyTree
        # cache-role overrides (build_weight_store reads those)
        self._cache_bits_by_rung: dict[int, Optional[int]] = {}
        if cache_bits is not None:
            for op in self.ladder:
                if cache_bits != "auto":
                    self._cache_bits_by_rung[op.bits] = cache_bits
                elif op.tree is not None and pol.tree_cache_bits(op.tree):
                    self._cache_bits_by_rung[op.bits] = None
                else:
                    self._cache_bits_by_rung[op.bits] = min(
                        int(op.b_x_tilde), 7)
        # The ladder is ONE weight store with zero-copy rung views: quantize
        # ONCE at the per-module max budget and realize every rung as a view
        # adding only small data leaves (activations quantized at the rung's
        # b~x, stored as data) — HBM independent of ladder depth, rung
        # budgets snapped to powers of two of the top rung, one pytree
        # structure and one set of avals, so every rung shares the one
        # compiled decode step (DESIGN.md §11). On a mesh it is sharded like
        # training params (``par``: the training ParallelConfig).
        needs_planes = dispatch.reads_planes(backend)
        rung_specs = {op.bits: (op.tree if op.tree is not None
                                else (op.r, op.b_x_tilde))
                      for op in self.ladder}
        if weight_store is not None:
            # serve a prebuilt store — typically artifact.load_artifact's
            # mmap-backed views (ROADMAP item 5: no re-quantization on the
            # serving host; fleet hosts all map ONE weights.bin). The store
            # must cover this engine's ladder; extra rungs are fine — a
            # rung-sharded fleet host serves a SUBSET of the artifact's
            # ladder (dist.sharding.rung_shard) from the same file.
            missing = [b for b in rung_specs if b not in weight_store.views]
            if missing:
                raise ValueError(
                    f"weight_store has no view for rung(s) {missing}; "
                    f"available: {sorted(weight_store.views)}")
            if needs_planes:
                leaf_names = {getattr(p[-1], "key", "") for p, _ in
                              jax.tree_util.tree_leaves_with_path(
                                  next(iter(weight_store.views.values())))}
                if "w_planes_pos" not in leaf_names:
                    raise ValueError(
                        "packed backend needs plane leaves; this weight "
                        "store was built without pack_planes")
            ws = serving.device_put_weight_store(
                serving.WeightStore(
                    store=weight_store.store,
                    views={b: weight_store.views[b] for b in rung_specs}),
                mesh=mesh, par=par)
            self.weight_store = ws.store
            self.variants = ws.views
        else:
            ws = serving.build_weight_store(
                params, cfg, rung_specs, mesh=mesh, par=par,
                pack_planes=needs_planes,
                cache_bits=self._cache_bits_by_rung or None)
            self.weight_store = ws.store
            self.variants = ws.views
        # offline block autotuning (kernels/autotune): measure-and-cache the
        # best Pallas block shapes per projection BEFORE the decode step is
        # ever traced — serving_linear then reads the cache at trace time,
        # so tuning never invalidates the one-compiled-decode-step claim
        # (all rungs share avals, hence shapes, hence tuning decisions)
        if autotune and dispatch.parse_backend(backend)[0] != "ref":
            self._autotune_projections()
        self._frontend_kwargs_fn = frontend_kwargs_fn

        # the packed planes one step reads, in place and through copies:
        # tallied from shapes each time the step is traced
        self.plane_bytes_in_place: Optional[int] = None
        self.plane_bytes_copied: Optional[int] = None

        def decode_step(p, s, t):
            with dispatch.plane_traffic() as tally:
                out = MD.decode_step(p, cfg, s, t)
            self.plane_bytes_in_place = tally["in_place"]
            self.plane_bytes_copied = tally["copied"]
            return out

        # a named function, so the trace names its module jit_decode_step
        self._step = jax.jit(decode_step)
        self.scheduler = Scheduler(self.ladder, self.max_batch)
        self.compilations_after_warmup: Optional[int] = None
        self.steps_by_rung = {op.bits: 0 for op in self.ladder}
        self.rung_switches = 0
        self.decode_steps = 0         # step_lane calls that ran a step
        self.prefill_steps = 0        # teacher-forced prompt positions
        self.live_rows = 0            # decode-step rows still wanting a token
        # 128-position cache blocks the attention calls read and hold,
        # tallied from each lane's length (``dispatch.kv_block_traffic``)
        self.kv_blocks_read: Optional[int] = None
        self.kv_blocks_cached: Optional[int] = None
        self._kv_caches: Optional[list] = None   # dispatch.kv_caches memo
        self._last_step_bits: Optional[int] = None
        self._macs_by_ctx: dict[int, Any] = {}   # macs_per_token memo
        self.recurrent_state_bytes = self._recurrent_state_bytes()

    def _recurrent_state_bytes(self) -> int:
        """Bytes of recurrent state one lane holds: the RWKV-6 wkv state
        and token shifts, the Mamba2 state and conv tail (KV caches are not
        recurrent state). From shapes alone; nothing is allocated."""
        if self._frontend_kwargs_fn is not None:
            return 0
        shapes = jax.eval_shape(
            lambda v: MD.init_decode_state(v, self.cfg, self.max_batch,
                                           self.max_len),
            next(iter(self.variants.values())))
        kinds = (R.RWKVState, S.SSMState)
        nodes = jax.tree_util.tree_leaves(
            shapes.caches, is_leaf=lambda n: isinstance(n, kinds))
        return int(sum(a.size * a.dtype.itemsize for n in nodes
                       if isinstance(n, kinds)
                       for a in jax.tree_util.tree_leaves(n)))

    def _count_kv_blocks(self, state, pos: int) -> None:
        """Add one decode step at cache position ``pos`` to the KV block
        counters (the caches' shapes are read from ``state`` once)."""
        if self._kv_caches is None:
            self._kv_caches = dispatch.kv_caches(state)
        traffic = dispatch.kv_block_traffic(self._kv_caches, pos)
        if traffic is not None:
            self.kv_blocks_read = (self.kv_blocks_read or 0) + traffic[0]
            self.kv_blocks_cached = (self.kv_blocks_cached or 0) + traffic[1]

    # -- offline autotuning -------------------------------------------------

    def _autotune_projections(self) -> None:
        """Tune every distinct projection shape in the (shape-identical)
        variants once, at the engine's decode row count. Idempotent: cached
        shapes short-circuit inside ``autotune.tune``."""
        variant = next(iter(self.variants.values()))
        seen: set = set()

        def walk(node):
            if isinstance(node, dict):
                if "w_q" in node:
                    sd = node["w_q"].ndim - 2    # scan-stacked leading dims
                    leaf = {k: (v[(0,) * sd]
                                if sd and getattr(v, "ndim", 0) >= sd else v)
                            for k, v in node.items()}
                    key = (leaf["w_q"].shape,
                           leaf["w_planes_pos"].shape[-3]
                           if "w_planes_pos" in leaf else None)
                    if key not in seen:
                        seen.add(key)
                        dispatch.tune_projection(self.max_batch, leaf,
                                                 self.backend)
                    return
                for v in node.values():
                    walk(v)
            elif isinstance(node, (list, tuple)):
                for v in node:
                    walk(v)

        walk(variant)

    # -- jit bookkeeping ----------------------------------------------------

    def _jit_cache_size(self) -> int:
        try:
            return int(self._step._cache_size())
        except Exception:
            return -1

    def warmup(self) -> None:
        """Run one decode step per rung so every compilation (there should
        be exactly one) happens before traffic."""
        state = self._init_state(self.ladder[0].bits)
        tok = jnp.zeros((self.max_batch, 1), jnp.int32)
        for op in self.ladder:
            jax.block_until_ready(
                self._step(self.variants[op.bits], state, tok)[0])
        self.compilations_after_warmup = self._jit_cache_size()
        self._warm_args = (self.variants[self.ladder[0].bits], state, tok)

    def pallas_calls_in_step(self) -> int:
        """Pallas TPU kernels (``tpu_custom_call``) in the compiled decode
        step — 0 when every projection ran through XLA, which a summary
        must show rather than hide. Lowering the warmed-up arguments again
        finds the executable warmup built; nothing recompiles."""
        if self.compilations_after_warmup is None:
            raise RuntimeError("call warmup() first")
        text = self._step.lower(*self._warm_args).compile().as_text()
        return text.count("tpu_custom_call")

    def assert_no_recompile(self) -> None:
        """After serving: the jit cache must not have grown past warmup."""
        if self.compilations_after_warmup is None:
            raise RuntimeError("call warmup() first")
        now = self._jit_cache_size()
        if now < 0 or self.compilations_after_warmup < 0:
            # fail loudly rather than silently skipping the central claim
            raise RuntimeError(
                "cannot verify the no-recompilation claim: jit cache "
                "introspection (_cache_size) is unavailable on this jax")
        if now > self.compilations_after_warmup:
            raise AssertionError(
                f"decode step recompiled while serving: "
                f"{self.compilations_after_warmup} -> {now} cache entries")

    # -- decode plumbing ----------------------------------------------------

    def _init_state(self, bits: int):
        kwargs = {}
        if self._frontend_kwargs_fn is not None:
            kwargs = self._frontend_kwargs_fn(self.max_batch)
        # the serving rung's variant: for encdec/vlm, init_decode_state runs
        # the encoder and projects cross-K/V through these weights, so the
        # frontend side must be quantized at the same rung as decode
        variant = self.variants[bits]
        return MD.init_decode_state(variant, self.cfg, self.max_batch,
                                    self.max_len, **kwargs)

    def _run_step(self, bits: int, state, tok):
        if self._last_step_bits is not None and bits != self._last_step_bits:
            self.rung_switches += 1
        self._last_step_bits = bits
        self.steps_by_rung[bits] += 1
        return self._step(self.variants[bits], state, tok)

    def _greedy(self, logits):
        v = self.cfg.vocab_size
        return jnp.argmax(logits[:, :, :v], axis=-1).astype(jnp.int32)

    def _pad_rows(self, rows: np.ndarray) -> np.ndarray:
        """Pad the request dim to max_batch (repeating row 0) so every wave
        presents identical avals to the jitted step."""
        if rows.shape[0] == self.max_batch:
            return rows
        pad = np.broadcast_to(rows[:1],
                              (self.max_batch - rows.shape[0],) + rows.shape[1:])
        return np.concatenate([rows, pad], axis=0)

    def prefill_wave(self, wave: Wave,
                     prefix_rows: Optional[np.ndarray] = None) -> Lane:
        """Teacher-force a wave's prompts and return its lane (the first
        generated token included).

        ``prefix_rows`` — (n_requests, prompt_len + done) int32 — replays a
        lane that already generated ``done`` tokens elsewhere: on a host
        restart (``dist.fault``) or a governor-forced rung switch the fleet
        rebuilds the lane here from prompt + tokens-so-far, and because the
        decode state is a pure function of the token prefix the rebuilt
        lane's continuation is bit-identical to the uninterrupted one
        (tests/test_fleet.py). The replayed wave's rung is THIS wave's rung
        — switching is replaying into a different rung's view.
        """
        reqs = wave.requests
        gen_max = max(r.max_new_tokens for r in reqs)
        if prefix_rows is None:
            rows, done = np.stack([r.prompt for r in reqs]), 0
        else:
            rows = np.asarray(prefix_rows, np.int32)
            done = rows.shape[1] - reqs[0].prompt_len
            if not 0 <= done < gen_max:
                raise ValueError(
                    f"replay prefix carries {done} generated tokens, "
                    f"wave needs 0 <= done < {gen_max}")
        if reqs[0].prompt_len + gen_max > self.max_len:
            raise ValueError(
                f"prompt_len {reqs[0].prompt_len} + gen {gen_max} exceeds "
                f"engine max_len {self.max_len}")
        bits = wave.rung.bits
        with _span("serve.prefill", wave):
            rows = jnp.asarray(self._pad_rows(rows), jnp.int32)
            with _span("serve.init_state", wave):
                state = self._init_state(bits)
            # teacher forcing: the prefix token by token through the step
            for i in range(rows.shape[1]):
                with _span("serve.dispatch", wave):
                    logits, state = self._run_step(bits, state,
                                                   rows[:, i:i + 1])
            self.prefill_steps += rows.shape[1]
            with _span("serve.sample", wave):
                tok = self._greedy(logits)
        return Lane(wave=wave, state=state, tok=tok, generated=[tok],
                    steps_left=gen_max - done - 1, done=done)
    def step_lane(self, lane: Lane) -> bool:
        """Advance a lane one decode step; True when the lane is finished.
        One step serves every live row of the wave — the fleet's unit of
        power-cap admission (each call costs the wave one token per
        request at its rung's bit-flip price)."""
        if lane.steps_left > 0:
            wave = lane.wave
            with _span("serve.step", wave):
                # rows whose request still wants a token: the lane holds
                # done + len(generated) tokens of each row so far
                have = lane.done + len(lane.generated)
                self.live_rows += sum(r.max_new_tokens > have
                                      for r in wave.requests)
                self.decode_steps += 1
                # this step writes and attends at the lane's length
                self._count_kv_blocks(
                    lane.state, wave.requests[0].prompt_len + have - 1)
                with _span("serve.dispatch", wave):
                    logits, lane.state = self._run_step(
                        wave.rung.bits, lane.state, lane.tok)
                with _span("serve.sample", wave):
                    lane.tok = self._greedy(logits)
                lane.generated.append(lane.tok)
                lane.steps_left -= 1
        return lane.steps_left <= 0

    def _rung_tree(self, rung) -> pol.PolicyTree:
        """The rung's PolicyTree: its layerwise tree, or the uniform lift
        of its single (b~x, R) point — one pricing path for both. With a
        quantized cache the tree additionally carries EXPLICIT cache-role
        overrides at the rung's resolved width, so
        ``policy.tree_power_per_token`` prices the act x act MACs at the
        cache's own bits (the per-response cache bit-flip line items)."""
        if rung.tree is not None:
            tree = rung.tree
        else:
            tree = pol.uniform_policy(pol.ModuleQuant(
                mode="pann", r=rung.r, b_x_tilde=rung.b_x_tilde))
        cb = self._cache_bits_by_rung.get(rung.bits)
        if cb is None:          # cache off, or policy-driven (tree has them)
            return tree
        ov = dict(tree.overrides)
        for role in pol.CACHE_PATHS:
            ov[role] = pol.cache_module_quant(cb)
        return pol.policy_tree(tree.default, ov)

    def ledger_for(self, rung, ctx: int) -> pw.EnergyLedger:
        macs = self._macs_by_ctx.get(ctx)
        if macs is None:
            macs = self._macs_by_ctx.setdefault(
                ctx, costs.macs_per_token(self.cfg, context_len=ctx))
        total, breakdown = pol.tree_power_per_token(
            self.profile, self._rung_tree(rung), act_macs=macs.act_macs)
        if rung.tree is None and self.cache_bits is None:
            # uniform rung, fp cache: keep the legacy headline number
            # bit-for-bit (same formula; the breakdown itemizes it). A
            # quantized cache re-prices the act x act half, so the
            # cache-aware total stands on its own there.
            total = pw.pann_token_bitflips(macs, rung.r, rung.b_x_tilde)
        return pw.EnergyLedger(total, breakdown_per_token=breakdown)
    def token_flips(self, bits: int, ctx: int) -> float:
        """Estimated bit flips of ONE token at rung ``bits`` with context
        ``ctx`` — the deterministic per-step price the fleet governor
        charges against its per-tick power grant before the step runs
        (admission control is pre-paid; that is what makes zero cap
        violations a structural property, not a measurement)."""
        return self.ledger_for(self.rungs[bits], ctx).bitflips_per_token

    def _finalize(self, lane: Lane) -> list[Response]:
        gen = np.asarray(jnp.concatenate(lane.generated, axis=1))
        rung = lane.wave.rung
        out = []
        for i, req in enumerate(lane.wave.requests):
            toks = gen[i, :req.max_new_tokens].tolist()
            ledger = self.ledger_for(rung, req.prompt_len
                                      + req.max_new_tokens)
            ledger.charge(len(toks))
            meta = {
                "rung_bits": rung.bits,
                "b_x_tilde": rung.b_x_tilde,
                "r": rung.r,
                "allocation": rung.allocation,
                "power_per_weight_mac": rung.power,
                **ledger.report(),
            }
            if self.cache_bits is not None:
                meta["cache_bits"] = pol.tree_cache_bits(
                    self._rung_tree(rung))
            out.append(Response(uid=req.uid, tokens=toks,
                                rung_bits=rung.bits, metadata=meta))
        return out

    # -- serving loops ------------------------------------------------------

    def generate(self, requests: Sequence[Request], max_lanes: int = 2
                 ) -> list[Response]:
        """Serve a batch of mixed-budget requests to completion.

        Lanes (one per admitted wave) advance round-robin one decode step at
        a time, so different rungs interleave between steps; finished lanes
        free a slot and the scheduler admits the next wave (continuous
        batching at wave granularity).
        """
        # validate the whole batch before any work: an oversized request or
        # an infeasible budget/floor combination must fail the call up
        # front, never mid-submit (stranding half the batch in the queue)
        # or mid-generate (discarding completed lanes' responses)
        resolved = []
        for r in requests:
            if r.prompt_len + r.max_new_tokens > self.max_len:
                raise ValueError(
                    f"request {r.uid}: prompt_len {r.prompt_len} + gen "
                    f"{r.max_new_tokens} exceeds engine max_len "
                    f"{self.max_len}")
            resolved.append(
                select_rung(self.ladder, r.power_budget_bits, r.min_score))
        for r, rung in zip(requests, resolved):
            self.scheduler.submit(r, rung=rung)
        lanes: list[Lane] = []
        responses: list[Response] = []
        while lanes or self.scheduler.pending():
            while len(lanes) < max_lanes:
                wave = self.scheduler.next_wave()
                if wave is None:
                    break
                lanes.append(self.prefill_wave(wave))
            for lane in list(lanes):
                if self.step_lane(lane):
                    responses.extend(self._finalize(lane))
                    lanes.remove(lane)
        return sorted(responses, key=lambda r: r.uid)

    def decode_stream(self, prompt: np.ndarray,
                      schedule: Sequence[tuple[int, int]]) -> dict:
        """Greedy-decode one stream whose rung changes mid-flight.

        ``schedule`` is ``[(bits, n_tokens), ...]``. A switch replays the
        accumulated prefix through the target rung's cached variant
        (teacher-forced, same jitted step — no re-quantization, no
        recompilation), then continues decoding; the continuation is
        therefore bit-identical to a fresh server at that rung given the
        same prefix (tested in tests/test_serve_engine.py).
        """
        prefix = [int(t) for t in np.asarray(prompt).reshape(-1)]
        prompt_len = len(prefix)
        total_gen = sum(n for _, n in schedule)
        if prompt_len + total_gen > self.max_len:
            raise ValueError("schedule exceeds engine max_len")
        for bits, _ in schedule:       # whole schedule up front, like the
            if bits not in self.rungs:  # length check — never mid-decode
                raise KeyError(f"no rung for {bits}-bit budget; "
                               f"ladder has {sorted(self.rungs)}")
        segments = []
        for bits, n in schedule:
            seg = []
            if n > 0:
                # a one-request wave through the serving calls generate()
                # makes, so the stream gets the same spans and counters
                req = Request(uid=0, prompt=np.asarray(prefix, np.int32),
                              max_new_tokens=n)
                lane = self.prefill_wave(Wave(rung=self.rungs[bits],
                                              requests=(req,)))
                while not self.step_lane(lane):
                    pass
                seg = lane.generated_rows()[0].tolist()
            prefix.extend(seg)
            segments.append({"rung_bits": bits, "tokens": seg})
        return {"tokens": prefix[prompt_len:], "segments": segments}

    # -- reporting ----------------------------------------------------------

    def describe(self) -> dict:
        total_macs = sum(m.macs for m in self.profile)
        return {
            "allocation": self.allocation,
            "backend": self.backend,
            "effective_backend": dispatch.effective_backend(self.backend),
            "cache_bits": self.cache_bits,
            "cache_bits_by_rung": dict(self._cache_bits_by_rung) or None,
            "ladder": [{"bits": op.bits, "b_x_tilde": op.b_x_tilde,
                        "r": round(op.r, 3),
                        "power_per_weight_mac": round(op.power, 2),
                        "total_gbitflips_per_token":
                            round(pw.giga(op.power * total_macs), 3)}
                       for op in self.ladder],
            "max_batch": self.max_batch,
            "max_len": self.max_len,
            "compilations_after_warmup": self.compilations_after_warmup,
            "steps_by_rung": dict(self.steps_by_rung),
            "rung_switches": self.rung_switches,
            "decode_steps": self.decode_steps,
            "prefill_steps": self.prefill_steps,
            "live_rows": self.live_rows,
            "recurrent_state_bytes": self.recurrent_state_bytes,
            "plane_bytes_in_place": self.plane_bytes_in_place,
            "plane_bytes_copied": self.plane_bytes_copied,
            "kv_blocks_read": self.kv_blocks_read,
            "kv_blocks_cached": self.kv_blocks_cached,
        }
