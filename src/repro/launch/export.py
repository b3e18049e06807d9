"""Train→serve export: freeze a trained checkpoint into a serving artifact
and PROVE the hand-off (CLI).

Loads a checkpoint written by ``launch/train.py``, rebuilds the training
run's final operating point (re-running the deterministic budget annealer /
layer-wise allocator when the run used ``--budget_schedule``), quantizes
the params for serving with the EMA-calibrated activation ranges frozen in
(``models.serving.quantize_params_for_serving(calib=...)``), and asserts
that the exported artifact reproduces the training-time held-out eval loss
to fp32 tolerance — the train→serve loop closes on numbers, not vibes.

    python -m repro.launch.train --arch llama3-8b --reduced --steps 120 \
        --quant pann --budget_schedule 0:fp,20:8,60:6 --ckpt_dir /tmp/ck
    python -m repro.launch.export --ckpt_dir /tmp/ck --out /tmp/artifact

The ``--out`` artifact directory uses the checkpoint layout (arrays.npz +
meta.json, atomic COMMITTED marker) so ``ckpt.checkpoint.restore`` loads it
straight into a serving tree: one operating point's artifact, as
``models.serving.quantize_params_for_serving`` builds it.

``--artifact_out`` additionally writes the mmap-able LADDER artifact
(``serve_engine.artifact``: manifest.json + weights.bin): one max-budget
weight store quantized from the same calibrated params, with a zero-copy
rung view per ``--artifact_ladder`` bit budget — the deployment form whose
weight HBM is independent of ladder depth (DESIGN.md §11, docs/artifact.md).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
from types import SimpleNamespace

import jax
import numpy as np

from repro.ckpt import checkpoint as ck
from repro.core import anneal
from repro.launch import steps as ST
from repro.launch import train as TR
from repro.launch.mesh import make_local_mesh
from repro.models import serving
from repro.serve_engine import artifact
from repro.serve_engine.ladder import build_ladder


def _final_operating_point(cfg, tcfg, targs, step: int):
    """(eval config, policy tree, uniform point, bits) at the end of
    training — the rung the artifact is exported at."""
    annealer = anneal.BudgetAnnealer.from_train_config(cfg, tcfg)
    if annealer is not None:
        bits = annealer.schedule.bits_at(max(step - 1, 0))
        if bits <= 0:
            raise SystemExit(
                "[export] the schedule ends in a full-precision segment — "
                "nothing to quantize; extend the schedule past its last "
                "fp knot or export an earlier checkpoint")
        tree = annealer.tree_for(bits)
        return dataclasses.replace(cfg, policy=tree), tree, None, bits
    # fixed operating point: the global (R, b~x) the run was configured with
    return cfg, None, (targs.r, targs.act_bits), 0


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ckpt_dir", required=True)
    ap.add_argument("--step", type=int, default=0,
                    help="checkpoint step to export (default: latest)")
    ap.add_argument("--out", default="",
                    help="write the serving artifact here (ckpt layout)")
    ap.add_argument("--artifact_out", default="",
                    help="write the mmap-able ladder weight store here "
                         "(manifest.json + weights.bin; "
                         "serve_engine.artifact)")
    ap.add_argument("--artifact_ladder", default="",
                    help="comma-separated bit budgets for the artifact's "
                         "rung views, e.g. 2,4,6 (default: the training "
                         "run's final operating point alone)")
    ap.add_argument("--tol", type=float, default=1e-3,
                    help="max |exported - training| eval-loss gap "
                         "(relative to the training loss)")
    args = ap.parse_args(argv)

    step = args.step or ck.latest_step(args.ckpt_dir)
    if step is None:
        raise SystemExit(f"[export] no checkpoint in {args.ckpt_dir}")
    meta = ck.read_meta(args.ckpt_dir, step)
    if "train_args" not in meta:
        raise SystemExit("[export] checkpoint meta lacks train_args "
                         "(written by a pre-export trainer?)")
    targs = SimpleNamespace(**meta["train_args"])
    cfg, tcfg, par = TR.build(targs)
    train_quant = TR.resolve_train_quant(targs)
    if targs.quant != "pann":
        raise SystemExit(f"[export] serving artifacts are PANN "
                         f"(checkpoint trained with --quant {targs.quant})")
    if cfg.tie_embeddings:
        raise SystemExit("[export] tied-embedding unembed has no separate "
                         "lm_head weight to quantize; untie to export")
    qat = train_quant == "qat"

    mesh = make_local_mesh(1)
    with mesh:
        key = jax.random.PRNGKey(targs.seed)
        template = jax.tree_util.tree_map(
            np.asarray, ST.make_train_state(key, cfg, tcfg, calibrate=qat))
        state = ck.restore(args.ckpt_dir, step, template,
                           strict=("calib/",))

        cfg_eval, tree, uniform_pt, bits = _final_operating_point(
            cfg, tcfg, targs, step)
        batch = TR.make_eval_batch(cfg, targs)

        # the training-time reference: the forward exactly as training ran
        # it — QAT fake-quant at the final operating point with activations
        # frozen to the calibrated EMA ranges, or plain fp for PTQ runs
        if qat:
            loss_train = ST.eval_loss(state.params, cfg_eval, batch,
                                      calib=state.calib)
        else:
            loss_train = ST.eval_loss(state.params,
                                      anneal.strip_quant(cfg), batch)

        calib = state.calib if qat else None
        if tree is not None:
            point = dict(policy=tree)
        else:
            point = dict(r=float(uniform_pt[0]), act_bits=int(uniform_pt[1]))
        variant = serving.quantize_params_for_serving(
            state.params, cfg, calib=calib, **point)

        # the exported artifact through the SERVING forward (the integer
        # dispatch path + frozen static activation ranges) on the same
        # held-out batch
        loss_serve = ST.eval_loss(variant, cfg_eval, batch)

    abs_diff = abs(loss_serve - loss_train)
    rel_diff = abs_diff / max(abs(loss_train), 1e-8)
    meta_eval = meta.get("eval_loss")
    summary = {
        "step": step, "bits": bits,
        "allocation": tcfg.budget_allocation if tcfg.budget_schedule
        else "uniform",
        "train_quant": train_quant,
        "loss_train_eval": loss_train, "loss_serve_eval": loss_serve,
        "abs_diff": abs_diff, "rel_diff": rel_diff,
        "meta_eval_loss": meta_eval,
    }
    if args.out:
        out_meta = {k: v for k, v in summary.items() if v is not None}
        out_meta["source_ckpt"] = args.ckpt_dir
        out_meta["train_args"] = meta["train_args"]
        path = ck.save(args.out, step, variant, meta=out_meta)
        summary["out"] = path
    if args.artifact_out:
        # the mmap-able ladder form: quantize ONCE at the max budget, one
        # zero-copy view per rung (models/serving.build_weight_store)
        if args.artifact_ladder:
            lad = build_ladder([int(b) for b in
                                args.artifact_ladder.split(",")],
                               d=float(cfg.d_model))
            specs = {op.bits: (op.tree if op.tree is not None
                               else (op.r, op.b_x_tilde)) for op in lad}
        elif tree is not None:
            specs = {bits: tree}
        else:
            specs = {0: (float(uniform_pt[0]),
                         None if uniform_pt[1] is None
                         else int(uniform_pt[1]))}
        ws = serving.build_weight_store(state.params, cfg, specs,
                                        pack_planes=True, calib=calib)
        summary["artifact_out"] = artifact.write_artifact(
            args.artifact_out, ws,
            meta={"source_ckpt": args.ckpt_dir, "step": step,
                  "rungs": sorted(specs),
                  "train_args": meta["train_args"]})
    print("[export] " + json.dumps(summary))

    if meta_eval is not None and qat and \
            abs(meta_eval - loss_train) > args.tol * max(abs(meta_eval), 1.0):
        raise SystemExit(
            f"[export] re-evaluated training loss {loss_train:.6f} drifted "
            f"from the checkpoint's recorded eval loss {meta_eval:.6f} — "
            f"the training forward is not reproducible")
    if qat and rel_diff > args.tol:
        raise SystemExit(
            f"[export] exported rung does NOT reproduce the training-time "
            f"eval loss: {loss_serve:.6f} vs {loss_train:.6f} "
            f"(rel {rel_diff:.2e} > tol {args.tol:.0e})")
    if qat:
        print(f"[export] round-trip OK: serving artifact reproduces the "
              f"training eval loss (rel diff {rel_diff:.2e})")
    else:
        print("[export] PTQ export (fp training reference; loss gap "
              f"{rel_diff:.2e} is the quantization cost, not gated)")
    return summary


if __name__ == "__main__":
    main()
