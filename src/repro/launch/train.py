"""End-to-end training driver (CLI).

Runs on whatever devices exist (1 CPU here; a pod slice in production):
deterministic synthetic data, AdamW, checkpoint/restart via the Supervisor,
straggler telemetry, power-aware QAT with budget annealing, optional
pipeline parallelism.

    PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --reduced \
        --steps 200 --quant pann --r 2.0

Power-aware QAT (DESIGN.md §9): ``--train_quant`` picks how quantization
meets training — ``none`` (fp), ``ptq`` (train fp, quantize only at
export/serve time), ``qat`` (STE fake-quant in the train step, activation
ranges EMA-calibrated into the train state). ``--budget_schedule`` anneals
the bit-flip budget through the run, re-running the layer-wise allocator at
every knot:

    python -m repro.launch.train --arch llama3-8b --reduced --steps 200 \
        --quant pann --train_quant qat --budget_schedule 0:fp,40:8,120:6 \
        --ckpt_dir /tmp/ck
    python -m repro.launch.export --ckpt_dir /tmp/ck --out /tmp/artifact
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.configs.base import ParallelConfig, QuantConfig, TrainConfig
from repro.ckpt import checkpoint as ck
from repro.core import anneal
from repro.core import calibrate as CAL
from repro.data.pipeline import SyntheticLM, frontend_stub
from repro.dist import sharding as SH
from repro.dist.fault import StepMonitor
from repro.launch import steps as ST
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.optim.optimizers import AdamWState

# held-out eval stream: same generator family as training, disjoint seed
EVAL_SEED_OFFSET = 1


def resolve_train_quant(args) -> str:
    """The explicit tri-state replacing the old ``args.quant != "none"``
    string-compare: none (fp training) | ptq (train fp, quantize at
    export) | qat (fake-quant in the train step). Unset derives the
    legacy behavior: qat whenever a quant mode is configured."""
    tq = args.train_quant or ("qat" if args.quant != "none" else "none")
    if tq != "none" and args.quant == "none":
        raise ValueError(
            f"--train_quant {tq} needs a quantization scheme; pass "
            f"--quant pann (or ruq/ruq_unsigned)")
    if tq == "none" and args.quant != "none":
        raise ValueError(
            f"--quant {args.quant} with --train_quant none is ambiguous: "
            f"use ptq (train fp, quantize at export) or qat")
    if args.budget_schedule:
        if tq != "qat":
            raise ValueError("--budget_schedule anneals QAT operating "
                             "points; requires --train_quant qat")
        if args.quant != "pann":
            raise ValueError("--budget_schedule plans PANN (b~x, R) "
                             "points; requires --quant pann")
    return tq


def build(args):
    tq = resolve_train_quant(args)
    qc = QuantConfig(mode=args.quant, r=args.r,
                     act_bits_tilde=args.act_bits, act_bits=args.act_bits,
                     weight_bits=args.weight_bits, qat=tq == "qat")
    cfg = configs.get_config(args.arch, quant=qc)
    if args.reduced:
        cfg = configs.reduced(cfg)
        cfg = dataclasses.replace(cfg, quant=qc)
    if args.d_model:
        cfg = dataclasses.replace(cfg, d_model=args.d_model,
                                  d_ff=args.d_ff or 4 * args.d_model,
                                  num_layers=args.layers or cfg.num_layers)
    horizon = args.total_steps or args.steps
    schedule = anneal.BudgetSchedule.parse(args.budget_schedule) \
        if args.budget_schedule else None
    tcfg = TrainConfig(lr=args.lr, total_steps=horizon,
                       warmup_steps=max(horizon // 20, 5), seed=args.seed,
                       budget_schedule=args.budget_schedule or None,
                       budget_allocation=args.allocation,
                       calib_decay=args.calib_decay,
                       anneal_warmup_steps=args.anneal_warmup,
                       lr_rewarmup_knots=schedule.knot_steps()
                       if schedule and args.anneal_warmup else ())
    par = ParallelConfig(fsdp=False, remat="block" if args.remat else "none",
                         microbatches=args.microbatches)
    return cfg, tcfg, par


TRAIN_ARG_KEYS = (
    "arch", "reduced", "d_model", "d_ff", "layers", "steps", "total_steps",
    "batch", "seq", "lr", "seed", "quant", "train_quant", "r", "act_bits",
    "weight_bits", "budget_schedule", "allocation", "calib_decay",
    "anneal_warmup", "remat", "microbatches",
)


def make_eval_batch(cfg, args) -> dict:
    """The deterministic held-out batch both the trainer and the exporter
    evaluate on (seed offset keeps it off the training stream)."""
    data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=args.seq,
                      global_batch=args.batch,
                      seed=args.seed + EVAL_SEED_OFFSET)
    batch = {k: jnp.asarray(v)
             for k, v in data.global_batch_arrays(0).items()}
    fe = frontend_stub(cfg, args.batch, 0, args.seed + EVAL_SEED_OFFSET)
    if fe is not None:
        key_name = ("enc_inputs" if cfg.family == "encdec"
                    else "image_embeds")
        batch[key_name] = jnp.asarray(fe)
    return batch


def init_sharded_state(key, cfg, tcfg, mesh, par, *, calibrate: bool):
    """(state, shardings): the TrainState initialized straight into its
    shardings on ``mesh``. Built whole on one device first, a full-width
    model's fp32 params + AdamW moments would not fit that device before
    they were spread."""
    init = partial(ST.make_train_state, cfg=cfg, tcfg=tcfg,
                   calibrate=calibrate)
    shapes = jax.eval_shape(init, key)
    pspecs = SH.param_specs(shapes.params, mesh, par)
    state_sh = SH.to_named(ST.TrainState(
        params=pspecs, opt=AdamWState(mu=pspecs, nu=pspecs, count=P()),
        step=P(), calib=jax.tree_util.tree_map(lambda _: P(), shapes.calib)),
        mesh)
    return jax.jit(init, out_shardings=state_sh)(key), state_sh


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--d_model", type=int, default=0)
    ap.add_argument("--d_ff", type=int, default=0)
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--steps", type=int, default=100,
                    help="steps to run in THIS invocation")
    ap.add_argument("--total_steps", type=int, default=0,
                    help="LR-schedule horizon (defaults to --steps); set it "
                         "when resuming so the schedule stays consistent")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--quant", default="none",
                    choices=["none", "ruq", "ruq_unsigned", "pann"])
    ap.add_argument("--train_quant", default="",
                    choices=["", "none", "ptq", "qat"],
                    help="none: fp training | ptq: train fp, quantize at "
                         "export | qat: STE fake-quant + EMA activation "
                         "calibration in the train step (default: qat "
                         "when --quant is set)")
    ap.add_argument("--r", type=float, default=2.0)
    ap.add_argument("--act_bits", type=int, default=8)
    ap.add_argument("--weight_bits", type=int, default=8)
    ap.add_argument("--budget_schedule", default="",
                    help="power-annealing knots 'step:bits,...' (bits = "
                         "unsigned-MAC budget, 'fp' = unquantized), e.g. "
                         "'0:fp,40:8,120:6'; replans the layer-wise "
                         "allocator at every knot (core/anneal.py)")
    ap.add_argument("--allocation", default="layerwise",
                    choices=["uniform", "layerwise"],
                    help="how annealed budgets are spent across modules")
    ap.add_argument("--calib_decay", type=float, default=0.99)
    ap.add_argument("--anneal_warmup", type=int, default=0,
                    help="LR re-warmup ramp (steps) after each budget knot")
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--model_axis", type=int, default=1)
    ap.add_argument("--ckpt_dir", default="")
    ap.add_argument("--ckpt_every", type=int, default=50)
    ap.add_argument("--log_every", type=int, default=10)
    args = ap.parse_args(argv)
    enable_compile_cache()

    try:
        cfg, tcfg, par = build(args)
    except ValueError as e:
        raise SystemExit(f"[train] {e}")
    train_quant = resolve_train_quant(args)
    qat = train_quant == "qat"
    annealer = anneal.BudgetAnnealer.from_train_config(cfg, tcfg)
    if annealer is not None:
        print(f"[train] budget schedule {annealer.schedule.describe()} "
              f"({tcfg.budget_allocation} allocation)")

    mesh = make_local_mesh(args.model_axis)
    data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=args.seq,
                       global_batch=args.batch, seed=args.seed)

    key = jax.random.PRNGKey(args.seed)

    def cfg_for_step(step):
        """The (config, plan, bits) governing ``step``: annealed when a
        schedule is set; stripped of quantization for fp/ptq training."""
        if annealer is not None:
            return annealer.config_at(cfg, step)
        if not qat:
            return anneal.strip_quant(cfg), None, None
        return cfg, None, None

    meta_args = {k: getattr(args, k) for k in TRAIN_ARG_KEYS}

    with mesh:
        state, state_sh = init_sharded_state(key, cfg, tcfg, mesh, par,
                                             calibrate=qat)

        monitor = StepMonitor()
        start_step = 0
        if args.ckpt_dir:
            last = ck.latest_step(args.ckpt_dir)
            if last is not None:
                tmpl = jax.tree_util.tree_map(np.asarray, state)
                state = ck.restore(args.ckpt_dir, last, tmpl, state_sh,
                                   strict=("calib/",))
                start_step = last
                print(f"[train] resumed from step {last}")
                if start_step >= args.steps:
                    raise SystemExit(
                        f"[train] checkpoint is already at step "
                        f"{start_step} >= --steps {args.steps}; raise "
                        f"--steps to continue or point --ckpt_dir at a "
                        f"fresh directory")

        segments = annealer.schedule.segments(start_step, args.steps) \
            if annealer is not None else ((start_step, args.steps, None),)

        losses = []
        plans_meta = []
        for seg_start, seg_end, seg_bits in segments:
            cfg_seg, plan, bits = cfg_for_step(seg_start)
            if annealer is not None:
                gbf = annealer.gbitflips_per_token(bits)
                label = "fp" if not bits else f"{bits}b"
                print(f"[train] segment [{seg_start}, {seg_end}): "
                      f"budget {label}, planned "
                      f"{gbf:.3f} Gbit-flips/token")
                if plan is not None:
                    print("[train] " + plan.describe())
                plans_meta.append({"step": seg_start, "bits": bits or 0,
                                   "gbitflips_per_token": gbf,
                                   "allocation": tcfg.budget_allocation})
            step_fn = jax.jit(
                partial(ST.train_step, cfg=cfg_seg, tcfg=tcfg, par=par),
                in_shardings=(state_sh, None), out_shardings=(state_sh, None),
                donate_argnums=(0,))

            for step in range(seg_start, seg_end):
                host = data.global_batch_arrays(step)
                batch = {k: jnp.asarray(v) for k, v in host.items()}
                fe = frontend_stub(cfg, args.batch, step, args.seed)
                if fe is not None:
                    key_name = ("enc_inputs" if cfg.family == "encdec"
                                else "image_embeds")
                    batch[key_name] = jnp.asarray(fe)
                t0 = time.monotonic()
                state, metrics = step_fn(state, batch)
                loss = float(metrics["loss"])
                monitor.record(step, time.monotonic() - t0)
                losses.append(loss)
                if step % args.log_every == 0 or step == args.steps - 1:
                    print(f"[train] step {step:5d} loss {loss:.4f} "
                          f"lr {float(metrics['lr']):.2e} "
                          f"gnorm {float(metrics['grad_norm']):.3f}")
                if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
                    ck.save(args.ckpt_dir, step + 1, state,
                            meta={"arch": cfg.name, "loss": loss,
                                  "train_args": meta_args})

        # deterministic held-out eval at the final operating point — the
        # number launch/export.py must reproduce from the serving artifact
        cfg_final, _, final_bits = cfg_for_step(max(args.steps - 1, 0))
        eval_l = ST.eval_loss(state.params, cfg_final,
                              make_eval_batch(cfg, args),
                              calib=state.calib)
        print(f"[train] eval loss {eval_l:.6f} (held-out batch, final "
              f"operating point)")
        if qat:
            host_calib = jax.tree_util.tree_map(np.asarray, state.calib)
            print("[train] " + CAL.describe(host_calib))

        if args.ckpt_dir:
            ck.save(args.ckpt_dir, args.steps, state,
                    meta={"arch": cfg.name, "loss": losses[-1],
                          "eval_loss": eval_l,
                          "final_bits": final_bits or 0,
                          "train_args": meta_args})
    summary = {"first_loss": losses[0], "last_loss": losses[-1],
               "steps": args.steps, "eval_loss": eval_l,
               "losses": [round(v, 6) for v in losses],
               "plans": plans_meta, **monitor.summary()}
    print("[train] " + json.dumps(summary))
    return summary


if __name__ == "__main__":
    main()
