"""Batched serving driver: prefill + decode loop with KV/state caches and
optional PANN-quantized weights (the deployment story of the paper: pick a
power budget, plan (b~x, R) with Algorithm 1, serve).

Single operating point (with --quant pann, the PANN serving artifact through
--backend):

    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-1.6b --reduced \
        --batch 4 --prompt_len 32 --gen 16 --quant pann --power_bits 4

Power-accuracy traversal (repro.serve_engine): plan a ladder of equal-power
operating points once, then pick the rung PER REQUEST from a declared power
budget — one process, one compiled step, many power levels:

    PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --reduced \
        --power_ladder 2,4,6 --budgets 4,2,6,6 --batch 4 --gen 16

Fleet under a global power cap (repro.serve_engine.fleet): N rung-sharded
decode hosts + a prefill host serving ONE mmap artifact, a telemetry-driven
governor holding aggregate Gbit-flips/sec under --global_budget:

    PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --reduced \
        --fleet_hosts 4 --global_budget 0.25 --ticks 12
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.configs.base import QuantConfig
from repro.core import costs, planner
from repro.data.pipeline import frontend_raw_stub, frontend_stub
from repro.kernels import dispatch
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as MD
from repro.models import serving
from repro.serve_engine import (EncodeEngine, EncodeRequest, Request,
                                ServeEngine)
from repro.serve_engine.fleet import (Fleet, FleetConfig, TrafficSpec,
                                      make_trace)


def plan_quant(args, total_macs: float | None = None) -> QuantConfig:
    if args.quant == "none":
        return QuantConfig(mode="none")
    if args.quant == "pann":
        budget = planner.budget_from_bits(args.power_bits)
        plan = planner.plan_with_theory(budget)
        # total network price (MACs x per-MAC power), not just per-MAC:
        # directly comparable with ladder / layerwise startup logs
        print(f"[serve] {plan.describe(total_macs=total_macs)}")
        return QuantConfig(mode="pann", r=plan.r,
                           act_bits_tilde=plan.b_x_tilde)
    return QuantConfig(mode=args.quant, weight_bits=args.power_bits,
                       act_bits=args.power_bits)


def serve_fleet(args) -> dict:
    """The fleet path: N simulated hosts under one global Gbit-flips/s cap."""
    ladder_bits = tuple(int(b) for b in
                        (args.power_ladder or "2,4,6").split(","))
    cfg = configs.get_config(args.arch, quant=QuantConfig(mode="none"))
    if args.reduced:
        cfg = configs.reduced(cfg)
    params = MD.init_params(jax.random.PRNGKey(args.seed), cfg)

    fc = FleetConfig(
        n_decode_hosts=args.fleet_hosts,
        n_prefill_hosts=1,
        ladder_bits=ladder_bits,
        allocation=args.allocation,
        cap_gbitflips_per_s=args.global_budget,
        max_batch=args.batch,
        max_len=args.prompt_len + args.gen + 2,
        backend=args.backend,
    )
    spec = TrafficSpec(seed=args.seed + 7, n_ticks=args.ticks,
                       prompt_lens=(args.prompt_len,),
                       gen_tokens=(max(args.gen - 4, 2), args.gen),
                       budget_mix=ladder_bits + (max(ladder_bits),))
    art_dir = args.artifact_dir or tempfile.mkdtemp(prefix="fleet_serve_")
    fleet = Fleet(cfg, fc, art_dir, params=params)
    trace = make_trace(spec, cfg.vocab_size, fleet.ladder)

    t0 = time.monotonic()
    report = fleet.run(trace)
    dt = time.monotonic() - t0
    fleet.assert_no_recompile()

    summary = {
        "arch": cfg.name,
        "mode": "fleet",
        "hosts": report["hosts"],
        "artifact_dir": art_dir,
        "cap_gbitflips_per_s": args.global_budget,
        "requests": report["requests"],
        "served": report["served"],
        "realized_gbitflips": report["realized_gbitflips"],
        "realized_gbitflips_per_s": report["realized_gbitflips_per_s"],
        "cap_violations": report["cap_violations"],
        "rung_token_histogram": report["rung_token_histogram"],
        "governor_replans": len(report["governor"]["replans"]),
        "wall_s": round(dt, 3),
    }
    print("[serve] " + json.dumps(summary))
    return summary


def serve_encode(args) -> dict:
    """The encoder path: batch-oriented item serving (no KV cache) through
    ``serve_engine.EncodeEngine`` — same ladder, same one-weight-store
    views, per-image/per-utterance power budgets."""
    ladder_bits = [int(b) for b in
                   (args.power_ladder or "2,4,6").split(",")]
    budgets = [int(b) for b in args.budgets.split(",")] if args.budgets \
        else ladder_bits
    cfg = configs.get_config(args.arch, quant=QuantConfig(mode="none"))
    if args.reduced:
        cfg = configs.reduced(cfg)
    params = MD.init_params(jax.random.PRNGKey(args.seed), cfg)

    engine = EncodeEngine(cfg, params, ladder_bits=ladder_bits,
                          max_batch=args.batch,
                          allocation=args.allocation,
                          backend=args.backend)
    engine.warmup()
    total_macs = sum(m.macs for m in engine.profile)
    for op in engine.ladder:
        if op.lw is not None:
            print(f"[serve] {op.describe()}")
        else:
            print(f"[serve] rung[{op.bits}b] "
                  f"{op.plan.describe(total_macs=total_macs)}")

    n = args.requests or args.batch
    raw = frontend_raw_stub(cfg, n, 0, args.seed)
    if raw is None:                 # no conv stem: stub embeddings
        raw = frontend_stub(cfg, n, 0, args.seed)
    reqs = [EncodeRequest(uid=i, item=raw[i],
                          power_budget_bits=budgets[i % len(budgets)])
            for i in range(n)]

    t0 = time.monotonic()
    responses = engine.encode(reqs)
    dt = time.monotonic() - t0
    engine.assert_no_recompile()

    summary = {
        "arch": cfg.name,
        "mode": "encode",
        "engine": engine.describe(),
        "items": [{"uid": r.uid, "rung_bits": r.rung_bits,
                   "encoded_shape": list(r.encoded.shape), **r.metadata}
                  for r in responses],
        "encoded": len(responses),
        "wall_s": round(dt, 3),
        "items_per_s": round(len(responses) / max(dt, 1e-9), 1),
    }
    print("[serve] " + json.dumps(summary))
    return summary


def serve_ladder(args) -> dict:
    """The traversal path: one ServeEngine, per-request rung selection."""
    ladder_bits = [int(b) for b in args.power_ladder.split(",")]
    budgets = [int(b) for b in args.budgets.split(",")] if args.budgets \
        else ladder_bits
    cfg = configs.get_config(args.arch, quant=QuantConfig(mode="none"))
    if args.reduced:
        cfg = configs.reduced(cfg)
    params = MD.init_params(jax.random.PRNGKey(args.seed), cfg)

    fe_fn = None
    if cfg.family in ("encdec", "vlm"):
        def fe_fn(batch):
            fe = frontend_stub(cfg, batch, 0, args.seed)
            key = "enc_inputs" if cfg.family == "encdec" else "image_embeds"
            return {key: jnp.asarray(fe)}

    max_len = args.prompt_len + args.gen
    cache_bits = None
    if args.cache_bits:
        cache_bits = "auto" if args.cache_bits == "auto" \
            else int(args.cache_bits)
    engine = ServeEngine(cfg, params, ladder_bits=ladder_bits,
                         max_batch=args.batch, max_len=max_len,
                         allocation=args.allocation,
                         backend=args.backend,
                         autotune=args.autotune,
                         cache_bits=cache_bits,
                         frontend_kwargs_fn=fe_fn)
    t0 = time.monotonic()
    engine.warmup()
    warmup_s = time.monotonic() - t0
    total_macs = sum(m.macs for m in engine.profile)
    for op in engine.ladder:
        if op.lw is not None:
            print(f"[serve] {op.describe()}")
        else:
            # same unit as the layerwise log: total network Gbit-flips
            print(f"[serve] rung[{op.bits}b] "
                  f"{op.plan.describe(total_macs=total_macs)}")

    rng = np.random.default_rng(args.seed)
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        args.prompt_len).astype(np.int32),
                    max_new_tokens=args.gen,
                    power_budget_bits=budgets[i % len(budgets)])
            for i in range(args.requests or args.batch)]

    t0 = time.monotonic()
    responses = engine.generate(reqs)
    dt = time.monotonic() - t0
    engine.assert_no_recompile()

    n_tok = sum(len(r.tokens) for r in responses)
    summary = {
        "arch": cfg.name,
        "mode": "ladder",
        "num_layers": cfg.num_layers,
        "d_model": cfg.d_model,
        "engine": engine.describe(),
        "pallas_calls_in_step": engine.pallas_calls_in_step(),
        "requests": [{"uid": r.uid, "rung_bits": r.rung_bits,
                      "token_ids": r.tokens, **r.metadata}
                     for r in responses],
        "generated": n_tok,
        "warmup_s": round(warmup_s, 3),
        "wall_s": round(dt, 3),
        "tok_per_s": round(n_tok / max(dt, 1e-9), 1),
    }
    print("[serve] " + json.dumps(summary))
    return summary


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt_len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--quant", default="none",
                    choices=["none", "ruq", "ruq_unsigned", "pann"])
    ap.add_argument("--power_bits", type=int, default=4,
                    help="power budget expressed as an unsigned-MAC bit width")
    ap.add_argument("--power_ladder", default="",
                    help="comma-separated bit budgets, e.g. 2,4,6 — serve a "
                         "multi-operating-point ladder (repro.serve_engine)")
    ap.add_argument("--allocation", default="uniform",
                    choices=["uniform", "layerwise"],
                    help="ladder rung allocation: one global (b~x, R) per "
                         "rung, or a per-module PolicyTree spending the "
                         "same total power layer-wise "
                         "(planner.allocate_layerwise)")
    ap.add_argument("--backend", default="packed",
                    choices=["ref", "fused", "packed", "fused:force",
                             "packed:force"],
                    help="serving-matmul backend (repro.kernels.dispatch): "
                         "ref (jnp integer oracle), fused (Pallas bit-plane "
                         "MXU matmul), packed (bit-packed planes, 8 "
                         "codes/byte along K; the default, which runs as "
                         "ref off TPU); ':force' runs Pallas in interpret "
                         "mode off-TPU. With --quant pann (no ladder) the "
                         "weights are materialized as the serving artifact "
                         "and decode runs through the chosen backend; other "
                         "--quant modes serve the training-form model.")
    ap.add_argument("--autotune", action="store_true",
                    help="measure-and-cache the best Pallas block shapes "
                         "per projection before warmup (kernels/autotune; "
                         "persistent per-device cache, $REPRO_AUTOTUNE_CACHE "
                         "overrides the location). Off-TPU the VMEM "
                         "heuristic is recorded untimed. Ladder mode only.")
    ap.add_argument("--cache_bits", default="",
                    help="quantize the decode-time KV cache (ladder mode): "
                         "an int in [2,7] pins every rung's cache width; "
                         "'auto' lets each rung pick — uniform rungs cache "
                         "at their own b~x, layerwise rungs let the "
                         "allocator trade cache bits against weight bits "
                         "under one budget. Decode attention then reads the "
                         "packed bit-plane cache directly "
                         "(kernels/pann_attention via --backend, jnp ref "
                         "oracle otherwise). Empty = fp cache.")
    ap.add_argument("--encode", action="store_true",
                    help="serve the ENCODER workload (vision/speech "
                         "frontends) instead of decode: whole-sequence "
                         "waves through serve_engine.EncodeEngine, no KV "
                         "cache, per-item power budgets resolved on the "
                         "same ladder. encdec/vlm archs only.")
    ap.add_argument("--budgets", default="",
                    help="per-request power budgets (bits), cycled over the "
                         "request stream; defaults to the ladder itself")
    ap.add_argument("--requests", type=int, default=0,
                    help="number of requests in ladder mode (default: --batch)")
    ap.add_argument("--fleet_hosts", type=int, default=0,
                    help="serve a simulated multi-host fleet with this many "
                         "rung-sharded decode hosts (+1 prefill host) under "
                         "--global_budget (repro.serve_engine.fleet)")
    ap.add_argument("--global_budget", type=float, default=0.25,
                    help="fleet mode: global power cap in Gbit-flips/sec, "
                         "enforced per tick by the fleet governor")
    ap.add_argument("--ticks", type=int, default=12,
                    help="fleet mode: length of the synthetic traffic trace")
    ap.add_argument("--artifact_dir", default="",
                    help="fleet mode: write/reuse the mmap serving artifact "
                         "here (default: a fresh temp dir)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.encode:
        return serve_encode(args)
    if args.fleet_hosts:
        return serve_fleet(args)
    if args.power_ladder:
        return serve_ladder(args)
    if args.allocation != "uniform":
        # only the ladder path consumes --allocation; refuse rather than
        # silently serve a uniform single point the user didn't ask for
        raise SystemExit(
            "--allocation layerwise requires --power_ladder (the "
            "single-point path has no per-module rungs)")
    if args.cache_bits:
        raise SystemExit(
            "--cache_bits requires --power_ladder (the quantized KV cache "
            "rides in the serve-engine weight store)")
    cfg = configs.get_config(args.arch)
    if args.reduced:
        cfg = configs.reduced(cfg)
    qc = plan_quant(args,
                    total_macs=costs.macs_per_token(cfg).weight_macs)
    cfg = dataclasses.replace(cfg, quant=qc)

    params = MD.init_params(jax.random.PRNGKey(args.seed), cfg)
    artifact = args.quant == "pann"
    if artifact:
        # the single operating point as the serving artifact (int8 codes;
        # packed plane leaves for 'packed' at the module's value-exact b_R):
        # every projection in the decode loop below dispatches through
        # repro.kernels.dispatch
        params = serving.quantize_params_for_serving(
            params, cfg, r=qc.r, act_bits=qc.act_bits_tilde,
            pack_planes=dispatch.reads_planes(args.backend))
        cfg = dataclasses.replace(cfg, kernel_backend=args.backend)
    rng = np.random.default_rng(args.seed)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)),
        jnp.int32)

    kwargs = {}
    fe = frontend_stub(cfg, args.batch, 0, args.seed)
    if fe is not None:
        kwargs["enc_inputs" if cfg.family == "encdec" else
               "image_embeds"] = jnp.asarray(fe)

    max_len = args.prompt_len + args.gen
    state = MD.init_decode_state(params, cfg, args.batch, max_len, **kwargs)
    step = jax.jit(lambda p, s, t: MD.decode_step(p, cfg, s, t))

    # prefill via teacher-forced decode (correct for every cache family)
    t0 = time.monotonic()
    logits = None
    for i in range(args.prompt_len):
        logits, state = step(params, state, prompts[:, i:i + 1])
    jax.block_until_ready(logits)
    prefill_time = time.monotonic() - t0

    # greedy decode
    t0 = time.monotonic()
    tok = jnp.argmax(logits[:, :, :cfg.vocab_size], axis=-1).astype(jnp.int32)
    out_tokens = [tok]
    for _ in range(args.gen - 1):
        logits, state = step(params, state, tok)
        tok = jnp.argmax(logits[:, :, :cfg.vocab_size],
                         axis=-1).astype(jnp.int32)
        out_tokens.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.monotonic() - t0

    gen = jnp.concatenate(out_tokens, axis=1)
    summary = {
        "arch": cfg.name,
        "quant": qc.mode,
        "backend": args.backend if artifact else None,
        "batch": args.batch,
        "generated": int(gen.shape[1]),
        "prefill_s": round(prefill_time, 3),
        "decode_s": round(t_decode, 3),
        "tok_per_s": round(args.batch * (args.gen - 1) / max(t_decode, 1e-9),
                           1),
        "sample": np.asarray(gen[0, :8]).tolist(),
    }
    print("[serve] " + json.dumps(summary))
    return summary


if __name__ == "__main__":
    main()
