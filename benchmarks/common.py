"""Shared benchmark utilities: timing, CSV emission, a small trainable LM."""
from __future__ import annotations

import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results")

# ---------------------------------------------------------------------------
# Per-device-kind peak table — the ONE copy of the hardware constants shared
# by the roofline analysis AND the roofline CI gate (benchmarks/roofline.py).
# Keys are the device_kind strings jax reports (a TPU v5e chip reports
# "TPU v5 lite"). Values per chip:
#   peak_flops   bf16 MXU peak (FLOP/s)
#   peak_int8    int8 MXU peak (OP/s) — the serving kernels' compute roof
#   hbm_bw       HBM bandwidth (byte/s)
#   ici_bw       ICI bandwidth per link (byte/s)
# Source for TPU v5e: Google Cloud documentation, "TPU v5e" (197 TFLOP/s
# bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
# interconnect over 4 links).
# ---------------------------------------------------------------------------

REFERENCE_KIND = "TPU v5 lite"

DEVICE_PEAKS = {
    "TPU v5 lite": {"peak_flops": 197e12, "peak_int8": 393e12,
                    "hbm_bw": 819e9, "ici_bw": 50e9},
    # interpret-mode hosts: placeholder roof so the analysis stays runnable
    # off-TPU (the CI gate never applies timing thresholds on these)
    "cpu": {"peak_flops": 1e12, "peak_int8": 2e12,
            "hbm_bw": 100e9, "ici_bw": 10e9},
}


def device_peaks(kind: str | None = None) -> dict:
    """Peaks for ``kind`` (default: the host's first device). A kind the
    table does not hold is an error: a roofline share priced against some
    other chip's peaks would be wrong without saying so."""
    if kind is None:
        kind = jax.devices()[0].device_kind
    if kind not in DEVICE_PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"add it to benchmarks.common.DEVICE_PEAKS "
                       f"(have {sorted(DEVICE_PEAKS)})")
    return {"device_kind": kind, **DEVICE_PEAKS[kind]}


def emit(name: str, us_per_call: float, derived: str = "") -> None:
    print(f"{name},{us_per_call:.1f},{derived}")


def save_json(name: str, obj) -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, name)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)
    return path


def time_call(fn, *args, iters: int = 3, warmup: int = 1) -> float:
    """Median wall time per call in microseconds (after warmup)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts) * 1e6)


# ---------------------------------------------------------------------------
# Small trainable LM for the accuracy benchmarks (Tables 2-4 analogues).
# The paper's benchmarks are ImageNet/CIFAR classifiers; our stand-in task is
# next-token classification on the structured synthetic stream — same
# protocol (train fp32 -> PTQ/QAT at matched power -> accuracy).
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TrainedLM:
    cfg: object
    params: dict
    val_tokens: jnp.ndarray
    val_labels: jnp.ndarray


def train_small_lm(steps: int = 250, seed: int = 0, arch: str = "llama3-8b",
                   vocab: int = 64, qat_quant=None, lr: float = 2e-3
                   ) -> TrainedLM:
    from repro import configs
    from repro.configs.base import QuantConfig, TrainConfig
    from repro.data.pipeline import SyntheticLM
    from repro.models import model as MD
    from repro.optim.optimizers import AdamW

    cfg = configs.reduced(configs.get_config(arch))
    cfg = dataclasses.replace(cfg, vocab_size=vocab, num_layers=2,
                              quant=qat_quant or QuantConfig(mode="none"))
    tcfg = TrainConfig(lr=lr, warmup_steps=10, total_steps=steps,
                       weight_decay=0.0)
    data = SyntheticLM(vocab_size=vocab, seq_len=64, global_batch=16,
                       seed=seed, structure=0.85)
    params = MD.init_params(jax.random.PRNGKey(seed), cfg)
    opt = AdamW(tcfg)
    ostate = opt.init(params)

    @jax.jit
    def step_fn(params, ostate, tokens, labels):
        loss, grads = jax.value_and_grad(
            lambda p: MD.lm_loss(p, cfg, tokens, labels, remat=False))(params)
        params, ostate, _ = opt.update(grads, ostate, params)
        return params, ostate, loss

    for step in range(steps):
        b = data.global_batch_arrays(step)
        params, ostate, loss = step_fn(params, ostate,
                                       jnp.asarray(b["tokens"]),
                                       jnp.asarray(b["labels"]))
    vb = data.global_batch_arrays(10_000)
    return TrainedLM(cfg=cfg, params=params,
                     val_tokens=jnp.asarray(vb["tokens"]),
                     val_labels=jnp.asarray(vb["labels"]))


def eval_accuracy(tl: TrainedLM, quant) -> float:
    """Top-1 next-token accuracy under a QuantConfig."""
    from repro.models import model as MD
    cfg = dataclasses.replace(tl.cfg, quant=quant)
    out = jax.jit(lambda p, t: MD.forward(p, cfg, t, remat=False))(
        tl.params, tl.val_tokens)
    pred = jnp.argmax(out.logits[..., :tl.cfg.vocab_size], axis=-1)
    mask = tl.val_labels >= 0
    return float((jnp.where(mask, pred == tl.val_labels, False)).sum()
                 / mask.sum())
