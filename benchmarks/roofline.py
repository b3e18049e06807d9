"""Roofline analysis from the dry-run artifacts — and the roofline CI gate.

Per (arch x shape) on the single-pod mesh:
    compute term    = HLO_FLOPs / (chips x bf16 peak)
    memory term     = HLO_bytes / (chips x HBM bandwidth)
    collective term = collective_bytes / (chips x ICI bandwidth per link)

HLO_FLOPs/bytes are the probe-corrected per-device values x chips (XLA's
cost_analysis counts while-loop bodies once; the dry-run probes fold trip
counts back in — see launch/dryrun.py). MODEL_FLOPS = 6·N·D (train) /
2·N·D (inference) with N the MoE-active parameter count.

Hardware peaks come from the per-device-kind table in ``benchmarks.common``
(DEVICE_PEAKS) — shared with the gate below, no hardcoded v5e constants.

``--check`` runs the ROOFLINE GATE (docs/kernels.md "reading the roofline
gate"): on TPU it times each Pallas serving backend on a prefill-shaped
projection and FAILS if the achieved int8 OP/s drop below the stated
fraction of the device's int8 MXU peak (GATE_THRESHOLDS; re-measured
per-device floors override via $REPRO_ROOFLINE_FLOORS — see
``gate_thresholds`` and docs/kernels.md "Re-measuring the roofline
floors"). Off-TPU the
timing gate skips cleanly — interpret-mode timings measure the emulator —
but the analysis invariants are still asserted so CPU CI catches formula
regressions the moment they land, not on the next TPU run.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import time

from benchmarks.common import (RESULTS_DIR, DEVICE_PEAKS, REFERENCE_KIND,
                               device_peaks, emit, save_json, time_call)

# Stated minimum fraction of the device's int8 MXU peak each Pallas backend
# must achieve on the gate's prefill-shaped projection (m=512, k=n=1024).
# fused streams 2P unpacked plane bytes per weight; packed trades HBM bytes
# for VPU unpack work, so its compute-roof floor is lower.
# Measurement procedure behind these numbers: docs/kernels.md
# "Re-measuring the roofline floors". Per-device re-measured floors can be
# applied without editing this file via $REPRO_ROOFLINE_FLOORS
# (gate_thresholds below).
GATE_THRESHOLDS = {"fused": 0.15, "packed": 0.08}
GATE_SHAPE = (512, 1024, 1024)     # (m, k, n): compute-visible, VMEM-safe

FLOORS_ENV = "REPRO_ROOFLINE_FLOORS"


def gate_thresholds() -> dict:
    """The floors the gate actually enforces: GATE_THRESHOLDS overlaid with
    $REPRO_ROOFLINE_FLOORS (a JSON object, e.g. '{"fused": 0.22}') so a
    re-measured device kind can tighten/loosen floors per-deployment
    without a source edit. Keys must name known backends and values must
    be fractions in (0, 1) — anything else fails loudly rather than
    silently gating on garbage."""
    raw = os.environ.get(FLOORS_ENV, "")
    if not raw:
        return dict(GATE_THRESHOLDS)
    try:
        override = json.loads(raw)
    except json.JSONDecodeError as e:
        raise SystemExit(
            f"[roofline-gate] ${FLOORS_ENV} is not valid JSON: {e}\n"
            f"  value: {raw!r}")
    if not isinstance(override, dict):
        raise SystemExit(
            f"[roofline-gate] ${FLOORS_ENV} must be a JSON object "
            f"{{backend: floor}}, got {type(override).__name__}")
    unknown = sorted(set(override) - set(GATE_THRESHOLDS))
    if unknown:
        raise SystemExit(
            f"[roofline-gate] ${FLOORS_ENV} names unknown backend(s) "
            f"{unknown}; known: {sorted(GATE_THRESHOLDS)}")
    for backend, floor in override.items():
        if not isinstance(floor, (int, float)) or isinstance(floor, bool) \
                or not 0.0 < float(floor) < 1.0:
            raise SystemExit(
                f"[roofline-gate] ${FLOORS_ENV}[{backend!r}] must be a "
                f"fraction of int8 peak in (0, 1), got {floor!r}")
    return {**GATE_THRESHOLDS,
            **{b: float(f) for b, f in override.items()}}


def analyze_record(r: dict, peaks: dict | None = None) -> dict | None:
    # dry-run artifacts are produced against the repo's reference part;
    # pass peaks= to re-price them for another device kind
    pk = peaks or device_peaks(REFERENCE_KIND)
    if r.get("skipped"):
        return {"arch": r["arch"], "shape": r["shape"],
                "skipped": r["skipped"]}
    n = r["n_devices"]
    # probe extrapolation can go slightly negative on near-zero terms
    flops_dev = max(r.get("flops_per_device_corrected",
                          r.get("flops_per_device", 0.0)), 0.0)
    bytes_dev = max(r.get("bytes_per_device_corrected",
                          r.get("bytes_per_device", 0.0)), 0.0)
    coll_dev = max(r.get("collective_bytes_corrected",
                         (r.get("collective_bytes_per_device") or {})
                         .get("total", 0.0)), 0.0)
    t_compute = flops_dev / pk["peak_flops"]
    t_memory = bytes_dev / pk["hbm_bw"]
    t_coll = coll_dev / pk["ici_bw"]
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    model_flops = r.get("model_flops_global", 0.0)
    hlo_global = flops_dev * n
    out = {
        "arch": r["arch"], "shape": r["shape"], "mesh": r["mesh"],
        "quant": r.get("quant", "none"),
        "device_kind": pk["device_kind"],
        "t_compute_s": t_compute, "t_memory_s": t_memory,
        "t_collective_s": t_coll,
        "dominant": dominant,
        "model_flops": model_flops,
        "hlo_flops_global": hlo_global,
        "useful_ratio": model_flops / hlo_global if hlo_global else 0.0,
        # roofline fraction: the useful fraction of the bound set by the
        # dominant term (what fraction of ideal-compute time the step needs)
        "roofline_fraction": (model_flops / pk["peak_flops"] / n)
        / max(max(terms.values()), 1e-30),
    }
    return out


def run(paths=("dryrun_both.json", "dryrun_single.json")) -> list:
    t0 = time.perf_counter()
    rows, seen = [], set()
    for p in paths:
        full = os.path.join(RESULTS_DIR, p)
        if not os.path.exists(full):
            continue
        with open(full) as f:
            data = json.load(f)
        for r in data.get("records", []):
            if r.get("mesh") == "multi":
                continue
            key = (r["arch"], r["shape"])
            if key in seen:
                continue
            seen.add(key)
            a = analyze_record(r)
            if a:
                rows.append(a)
    save_json("roofline.json", rows)
    us = (time.perf_counter() - t0) * 1e6
    live = [r for r in rows if "skipped" not in r]
    if live:
        worst = min(live, key=lambda r: r["roofline_fraction"])
        emit("roofline", us,
             f"{len(live)} cells; worst fraction "
             f"{worst['roofline_fraction']:.3f} ({worst['arch']} x "
             f"{worst['shape']})")
    else:
        emit("roofline", us, "no dry-run records yet — run launch/dryrun")
    return rows


def markdown_table(rows: list) -> str:
    live = [r for r in rows if "skipped" not in r]
    lines = ["| arch | shape | compute s | memory s | collective s | "
             "dominant | useful | roofline frac |",
             "|---|---|---|---|---|---|---|---|"]
    for r in sorted(live, key=lambda x: (x["arch"], x["shape"])):
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['t_compute_s']:.3e} | "
            f"{r['t_memory_s']:.3e} | {r['t_collective_s']:.3e} | "
            f"{r['dominant']} | {r['useful_ratio']:.2f} | "
            f"{r['roofline_fraction']:.3f} |")
    skipped = [r for r in rows if "skipped" in r]
    for r in skipped:
        lines.append(f"| {r['arch']} | {r['shape']} | — | — | — | skipped: "
                     f"{r['skipped']} | — | — |")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# The CI gate
# ---------------------------------------------------------------------------

def assert_invariants(rows: list | None = None) -> None:
    """Platform-independent sanity of the roofline math + peaks table —
    asserted on every gate run, TPU or not, so formula regressions fail CPU
    CI immediately."""
    for kind, pk in DEVICE_PEAKS.items():
        assert all(v > 0 for v in pk.values()), (kind, pk)
        assert pk["peak_int8"] >= pk["peak_flops"], (
            f"{kind}: int8 MXU peak below bf16 peak")
    synthetic = {
        "arch": "synthetic", "shape": "s", "mesh": "single", "n_devices": 4,
        "flops_per_device_corrected": 1e12, "bytes_per_device_corrected":
        1e9, "collective_bytes_corrected": 1e8, "model_flops_global": 3e12,
    }
    checks = [analyze_record(synthetic)]
    checks += [r for r in (rows or []) if "skipped" not in r]
    for a in checks:
        terms = (a["t_compute_s"], a["t_memory_s"], a["t_collective_s"])
        assert all(t >= 0 and math.isfinite(t) for t in terms), a
        assert a["dominant"] in ("compute", "memory", "collective"), a
        assert math.isfinite(a["roofline_fraction"]), a
        assert a["roofline_fraction"] >= 0, a
    # the synthetic record is hand-checkable: compute 1s, memory ~1.22ms,
    # collective 2ms on v5e — compute-dominant with useful fraction 3/4
    a = checks[0]
    assert a["dominant"] == "compute", a
    assert abs(a["useful_ratio"] - 0.75) < 1e-9, a


def _gate_measurements() -> dict:
    """Time each Pallas serving backend on the gate shape; returns
    {backend: {us, achieved_int8_ops, fraction_of_peak}}. TPU only."""
    import jax.numpy as jnp
    import numpy as np
    from repro import configs
    from repro.kernels import dispatch
    from repro.models.serving import quantize_params_for_serving

    m, k, n = GATE_SHAPE
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    cfg = configs.reduced(configs.get_config("llama3-8b"))
    leaf = quantize_params_for_serving(
        {"wq": {"w": w}}, cfg, r=2.0, act_bits=8, pack_planes=True)["wq"]
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    peaks = device_peaks()
    out = {}
    for backend in GATE_THRESHOLDS:
        us = time_call(lambda b=backend: dispatch.serving_linear(x, leaf, b),
                       iters=5)
        ops_per_call = 2.0 * m * k * n
        achieved = ops_per_call / (us * 1e-6)
        out[backend] = {
            "us": round(us, 1),
            "achieved_int8_ops": achieved,
            "fraction_of_peak": achieved / peaks["peak_int8"],
        }
    return out


def gate(check: bool = True) -> dict:
    """The roofline CI gate. Returns (and saves) the gate record; raises
    SystemExit(1) on a threshold breach when ``check``."""
    from repro.kernels import ops as _kops

    rows = run()
    assert_invariants(rows)
    peaks = device_peaks()
    floors = gate_thresholds()
    record = {"device": peaks, "thresholds": floors,
              "shape": list(GATE_SHAPE)}
    if floors != GATE_THRESHOLDS:
        # make an overridden gate self-describing in the CI artifact
        record["floors_overridden_via"] = FLOORS_ENV
        print(f"[roofline-gate] floors overridden via ${FLOORS_ENV}: "
              f"{floors}")
    failures = []
    if _kops.on_tpu():
        meas = _gate_measurements()
        record["measurements"] = meas
        for backend, rec in meas.items():
            frac = rec["fraction_of_peak"]
            floor = floors[backend]
            line = (f"{backend}: {frac:.3f} of int8 peak "
                    f"(floor {floor:.2f}, {rec['us']:.0f} us)")
            print(f"[roofline-gate] {line}")
            if frac < floor:
                failures.append(line)
    else:
        record["skipped"] = ("no TPU — interpret-mode timings measure the "
                             "emulator; invariants asserted instead")
        print(f"[roofline-gate] {record['skipped']}")
    record["failures"] = failures
    save_json("roofline_gate.json", record)
    if check and failures:
        for f in failures:
            print(f"[roofline-gate] BELOW ROOFLINE FLOOR: {f}")
        raise SystemExit(1)
    if check:
        print("[roofline-gate] passed")
    return record


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true",
                    help="run as the CI gate: fail when a Pallas backend "
                         "drops below its stated fraction of the int8 MXU "
                         "peak (TPU); off-TPU, assert analysis invariants "
                         "and skip the timing gate cleanly")
    args = ap.parse_args()
    if args.check:
        gate(check=True)
    else:
        print(markdown_table(run()))
