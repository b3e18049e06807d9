"""Chip smoke test: the main path, once, on the chip, at full published width.

    python chip_smoke.py              # one chip: PANN serving of zamba2-1.2b
    python chip_smoke.py --chips 4    # four chips: sharded QAT training

One chip (the default). ``repro.launch.serve.main`` serves four requests
over a three-rung power ladder with the quantized KV cache, through the
``packed`` Pallas kernels; then the same requests again through the jnp
``ref`` backend on the same seed. Every backend realizes one integer
dataflow (kernels/dispatch.py), so every generated token must agree. The
packed run must hold Pallas kernels (``tpu_custom_call``) in its compiled
decode step, and neither run may compile again after warmup.

Four chips. ``repro.launch.train.main`` takes three QAT steps of
zamba2-1.2b on a (2, 2) data x model mesh: its fp32 params and AdamW
moments (~16 B/param, ~19 GB) do not fit one chip. Every chip's peak
memory must show its share of the state. Then one step of the reduced
config on that mesh (its loss and gradient norm) is compared with the
same step on one device.

Everything runs in this one process: the chip belongs to one process at a
time. The last line of stdout is ``{"ok": true, "device": {...}}``; any
failure exits nonzero without it, and so does a host without a TPU.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "zamba2-1.2b"
SERVE_ARGS = ["--arch", ARCH, "--power_ladder", "2,4,6",
              "--budgets", "4,2,6,6", "--cache_bits", "auto",
              "--batch", "4", "--prompt_len", "32", "--gen", "16"]
# --remat: without it the full-width step needs ~25 GB per chip (a v5e
# compile of this step); with it, ~12.5 GB of a chip's 16 GB
TRAIN_ARGS = ["--arch", ARCH, "--quant", "pann", "--train_quant", "qat",
              "--model_axis", "2", "--batch", "8", "--seq", "512",
              "--steps", "3", "--log_every", "1", "--remat"]
# the sharded-vs-one-device tolerance of
# tests/test_dist_multidev.py::test_sharded_train_step_matches_single_device
LOSS_RTOL = 2e-3


class SmokeFailure(Exception):
    pass


def log(**fields) -> None:
    print("[chip_smoke] " + json.dumps(fields), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def peak_bytes(dev) -> int:
    return int(dev.memory_stats()["peak_bytes_in_use"])


def serve(backend: str) -> dict:
    import jax
    from repro.launch import serve as serve_cli
    t0 = time.monotonic()
    summary = serve_cli.main(SERVE_ARGS + ["--backend", backend])
    eng = summary["engine"]
    log(phase=f"serve[{backend}]", arch=summary["arch"],
        num_layers=summary["num_layers"], d_model=summary["d_model"],
        effective_backend=eng["effective_backend"],
        pallas_calls_in_step=summary["pallas_calls_in_step"],
        compilations_after_warmup=eng["compilations_after_warmup"],
        warmup_s=summary["warmup_s"],
        tok_per_s_informational=summary["tok_per_s"],
        phase_s=round(time.monotonic() - t0, 3),
        peak_bytes_in_use=peak_bytes(jax.devices()[0]))
    return summary


def one_chip() -> None:
    from repro import configs
    full = configs.get_config(ARCH)
    packed = serve("packed")
    check((packed["num_layers"], packed["d_model"])
          == (full.num_layers, full.d_model),
          f"served {packed['num_layers']} layers x {packed['d_model']}, "
          f"not the full {full.num_layers} x {full.d_model}")
    check(packed["engine"]["effective_backend"] == "packed",
          f"packed run fell back to {packed['engine']['effective_backend']}")
    check(packed["pallas_calls_in_step"] > 0,
          "no tpu_custom_call in the packed decode step")
    ref = serve("ref")
    check(ref["engine"]["effective_backend"] == "ref", "ref run not on ref")
    got = {r["uid"]: r["token_ids"] for r in packed["requests"]}
    want = {r["uid"]: r["token_ids"] for r in ref["requests"]}
    check(len(got) == 4 and got.keys() == want.keys(),
          f"requests served: packed {sorted(got)}, ref {sorted(want)}")
    gen = int(SERVE_ARGS[SERVE_ARGS.index("--gen") + 1])
    for uid in sorted(want):
        same = got[uid] == want[uid]
        log(phase="compare", uid=uid, tokens_equal=same, packed=got[uid],
            ref=want[uid])
        check(same and len(got[uid]) == gen,
              f"request {uid}: packed tokens differ from ref")


def reduced_step(mesh) -> list[float]:
    """[loss, grad_norm] of one QAT train step of the reduced config on
    ``mesh``, through the trainer's own state init and step."""
    from functools import partial
    import jax
    import jax.numpy as jnp
    from repro import configs
    from repro.configs.base import ParallelConfig, QuantConfig, TrainConfig
    from repro.data.pipeline import SyntheticLM
    from repro.launch import steps as ST
    from repro.launch.train import init_sharded_state
    cfg = configs.reduced(configs.get_config(
        ARCH, quant=QuantConfig(mode="pann", qat=True)))
    tcfg = TrainConfig(lr=1e-3, warmup_steps=2, total_steps=10)
    par = ParallelConfig(remat="none")
    data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=16,
                       global_batch=8, seed=0)
    batch = {k: jnp.asarray(v) for k, v in data.global_batch_arrays(0).items()}
    with mesh:
        state, state_sh = init_sharded_state(
            jax.random.PRNGKey(0), cfg, tcfg, mesh, par, calibrate=True)
        step = jax.jit(partial(ST.train_step, cfg=cfg, tcfg=tcfg, par=par),
                       in_shardings=(state_sh, None),
                       out_shardings=(state_sh, None))
        _, metrics = step(state, batch)
    return [float(metrics["loss"]), float(metrics["grad_norm"])]


def four_chips() -> None:
    import jax
    from repro.launch import train as train_cli
    from repro.launch.mesh import make_local_mesh
    devs = jax.devices()
    check(len(devs) == 4, f"--chips 4 needs 4 devices, have {len(devs)}")
    t0 = time.monotonic()
    summary = train_cli.main(TRAIN_ARGS)
    peaks = [peak_bytes(d) for d in devs]
    log(phase="train", arch=ARCH, losses=summary["losses"],
        eval_loss=summary["eval_loss"], mean_step_s=summary.get("mean_step_s"),
        phase_s=round(time.monotonic() - t0, 3), peak_bytes_in_use=peaks)
    check(len(summary["losses"]) == 3
          and all(math.isfinite(v) for v in summary["losses"]),
          f"QAT losses not finite: {summary['losses']}")
    # params and moments sharded over "model", batch over "data": every
    # chip holds a like share — none parks the whole state
    check(min(peaks) >= 0.5 * max(peaks),
          f"per-chip peaks unbalanced: {peaks}")

    # [loss, grad_norm]: the step's forward and its backward
    sharded = reduced_step(make_local_mesh(2, devs))
    single = reduced_step(make_local_mesh(1, devs[:1]))
    rel = [abs(a - b) / abs(b) for a, b in zip(sharded, single)]
    log(phase="compare", mesh_2x2=sharded, mesh_1x1=single, rel_err=rel,
        rtol=LOSS_RTOL)
    check(max(rel) <= LOSS_RTOL, "sharded step disagrees with one device")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serving on one chip; 4: sharded training and "
                         "its one-device comparison, nothing else")
    args = ap.parse_args(argv)
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"[chip_smoke] FAIL: the repo's package is not here ({e})",
              file=sys.stderr)
        return 2
    enable_compile_cache()
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"[chip_smoke] FAIL: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    try:
        (four_chips if args.chips == 4 else one_chip)()
    except Exception as e:
        print(f"[chip_smoke] FAIL: {type(e).__name__}: {e}", file=sys.stderr)
        raise
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
