"""Encoder serving benchmark + the CI encoder correctness gate.

Covers the PR-10 workload: conv-stem frontends (vision patchify, speech
feature extractor) served through the quantized conv projection and the
batch-oriented ``EncodeEngine``. Two jobs:

  * timings — items/s per ladder rung for each reduced encoder arch.
    INFORMATIONAL only (CPU interpret-mode hosts are noisy); never gated.
  * ``--check`` — gate the platform-independent invariants against the
    committed baseline (benchmarks/baselines/encoder_bench.json):

      - conv parity is EXACT: ``dispatch.serving_conv`` bit-identical to
        the jnp int32 conv oracle on every backend (ref / fused / packed)
        AND on every rung VIEW of one weight store;
      - the engine's one-compiled-encode-step claim: exactly one jit cache
        entry after warming the whole ladder, zero growth after serving
        mixed-budget traffic (``assert_no_recompile``);
      - structural invariants (encoder token counts, conv role sets,
        per-item Gbit-flips per rung) match the baseline — refresh by
        copying benchmarks/results/encoder_bench.json over it when the
        geometry or cost model legitimately changes.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.common import emit, save_json  # noqa: E402
from repro import configs  # noqa: E402
from repro.core import costs  # noqa: E402
from repro.data import pipeline  # noqa: E402
from repro.kernels import dispatch  # noqa: E402
from repro.models import model as MD  # noqa: E402
from repro.models import serving  # noqa: E402
from repro.serve_engine import EncodeEngine, EncodeRequest  # noqa: E402

BASELINE = os.path.join(os.path.dirname(__file__), "baselines",
                        "encoder_bench.json")
ARCHS = ("llama-3.2-vision-90b", "seamless-m4t-medium")
BACKENDS = ("ref", "fused:force", "packed:force")
LADDER = (2, 4, 6)


def _exact(a, b) -> dict:
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return {"exact": bool((a == b).all()),
            "max_abs_diff": float(np.abs(a - b).max())}


def _conv_parity(cfg, params, raw) -> dict:
    """serving_conv vs the int32 oracle: per backend on a single-point
    artifact, and per rung view of one plane-packed weight store."""
    out = {}
    spec0 = cfg.conv_stem[0]
    for backend in BACKENDS:
        sp = serving.quantize_params_for_serving(
            params, cfg, r=4.0, act_bits=6,
            pack_planes=dispatch.reads_planes(backend))
        p = sp["conv_stem"]["s0"]
        y = dispatch.serving_conv(raw, p, spec0, backend)
        out[f"backend:{backend}"] = _exact(
            y, dispatch.serving_conv_oracle(raw, p, spec0))
    ws = serving.build_weight_store(
        params, cfg, {2: (2.0, 6), 6: (16.0, 6)}, pack_planes=True)
    for rung, view in ws.views.items():
        p = view["conv_stem"]["s0"]
        y = dispatch.serving_conv(raw, p, spec0, "packed:force")
        out[f"view:{rung}b"] = _exact(
            y, dispatch.serving_conv_oracle(raw, p, spec0))
    return out


def _engine_run(cfg, params, raw) -> dict:
    eng = EncodeEngine(cfg, params, ladder_bits=LADDER, max_batch=2,
                       backend="ref", allocation="layerwise")
    eng.warmup()
    budgets = [2, 4, 6, 6, 2, 4]
    reqs = [EncodeRequest(uid=i, item=np.asarray(raw[i % raw.shape[0]]),
                          power_budget_bits=b)
            for i, b in enumerate(budgets)]
    t0 = time.perf_counter()
    responses = eng.encode(reqs)
    dt = time.perf_counter() - t0
    eng.assert_no_recompile()
    conv_roles = sorted(
        k for k in responses[0].metadata["per_module_gbitflips_per_token"]
        if k.startswith("conv."))
    return {
        "compilations_after_warmup": eng.compilations_after_warmup,
        "recompiled": False,
        "conv_roles": conv_roles,
        "encoder_tokens": costs.encoder_tokens(cfg),
        "gflips_per_item_by_rung": {
            str(b): round(float(eng.item_flips(b)) / 1e9, 6)
            for b in LADDER},
        "items_per_s": round(len(responses) / max(dt, 1e-9), 1),
        "rung_bits_served": sorted({r.rung_bits for r in responses}),
    }


def run(check: bool = False) -> dict:
    result = {}
    for arch in ARCHS:
        cfg = configs.reduced(configs.get_config(arch))
        params = MD.init_params(jax.random.PRNGKey(0), cfg)
        raw = jnp.asarray(pipeline.frontend_raw_stub(cfg, 2, step=0))
        t0 = time.perf_counter()
        parity = _conv_parity(cfg, params, raw)
        engine = _engine_run(cfg, params, raw)
        emit(f"encoder_bench/{arch}", (time.perf_counter() - t0) * 1e6,
             f"{engine['items_per_s']} items/s; "
             f"{len(parity)} parity checks")
        result[arch] = {"conv_parity": parity, "engine": engine}
    save_json("encoder_bench.json", result)
    return result


def check_baseline(result: dict, baseline_path: str = BASELINE
                   ) -> list[str]:
    failures = []
    # parity is EXACT by construction — gate it regardless of any baseline
    for arch, rec in result.items():
        for name, par in rec["conv_parity"].items():
            if not par["exact"]:
                failures.append(
                    f"{arch} conv parity {name}: NOT bit-identical "
                    f"(max abs diff {par['max_abs_diff']})")
        eng = rec["engine"]
        if eng["compilations_after_warmup"] != 1:
            failures.append(
                f"{arch}: {eng['compilations_after_warmup']} compilations "
                f"after warming the ladder (want exactly 1)")
    if not os.path.exists(baseline_path):
        failures.append(f"missing committed baseline {baseline_path}")
        return failures
    with open(baseline_path) as f:
        base = json.load(f)
    # structural invariants; throughput (items_per_s) is informational
    gated = ("conv_roles", "encoder_tokens", "gflips_per_item_by_rung",
             "rung_bits_served")
    for arch, brec in base.items():
        if arch.startswith("_"):
            continue
        if arch not in result:
            failures.append(f"baseline arch {arch} missing from run")
            continue
        eng, beng = result[arch]["engine"], brec["engine"]
        for key in gated:
            if eng[key] != beng[key]:
                failures.append(
                    f"{arch} {key} drifted from baseline: {eng[key]} != "
                    f"{beng[key]} — refresh {baseline_path} if intended")
    return failures


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true",
                    help="gate conv parity (EXACT), the no-recompile "
                         "claim, and the structural baseline")
    args = ap.parse_args(argv)
    result = run(check=args.check)
    if args.check:
        failures = check_baseline(result)
        if failures:
            for f in failures:
                print(f"[encoder_bench] FAIL: {f}")
            raise SystemExit(1)
        print("[encoder_bench] parity exact; baseline check passed")
    return result


if __name__ == "__main__":
    main()
