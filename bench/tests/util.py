"""Cells cut to CPU size for the tests: a configuration file of the
benchmark with the program's reduced configuration of the same
architecture (in the file's form: its activation, its heads as wide
against the model width), the `ref` backend and a few small requests."""
from __future__ import annotations

import dataclasses
import json
import os

from bench import harness

SMALL_MIX = {
    "closed": {"loop": "closed", "clients": 6, "pool": 48,
               "prompt_len": {"kind": "fixed", "value": 4},
               "output_len": {"kind": "lognormal", "median": 6, "sigma": 0.5,
                              "min": 3, "max": 10},
               "budgets": {"bits": [2, 4, 6], "shares": [1, 1, 1]},
               "trace_seconds": 1.0},
    "open": {"loop": "open", "rate": 4.0,
             "burst": {"factor": 4.0, "seconds": 0.5, "gap_mean_s": 2.0},
             "horizon_s": 10.0,
             "prompt_len": {"kind": "fixed", "value": 4},
             "output_len": {"kind": "lognormal", "median": 5, "sigma": 0.5,
                            "min": 3, "max": 8},
             "budgets": {"bits": [2, 4, 6], "shares": [0.3, 0.4, 0.3]},
             "trace_seconds": 1.0},
}


def reduced_cell(config: str, loop: str = "closed", limit: float = 0.1,
                 backend: str = "ref", max_batch: int = 3):
    """(Cell, ModelConfig) of bench/configs/<config>.json at reduced size."""
    from repro import configs
    from repro.serve_engine.ladder import build_ladder
    with open(os.path.join(harness.BENCH_DIR, "configs",
                           config + ".json")) as f:
        spec = json.load(f)
    cfg = configs.reduced(harness.model_config(spec))
    if spec.get("head_dim"):
        # heads as much wider than d_model / num_heads as the file's
        widen = spec["head_dim"] * spec["num_heads"] // spec["d_model"]
        cfg = dataclasses.replace(
            cfg, head_dim=widen * cfg.d_model // cfg.num_heads)
    for k in harness.MODEL_KEYS:
        spec[k] = getattr(cfg, k)
    spec.update(backend=backend, max_batch=max_batch)
    pts = {}
    for op in build_ladder(spec["ladder_bits"], d=float(cfg.d_model)):
        pts[str(op.bits)] = {"r": op.r, "b_x_tilde": op.b_x_tilde}
        if spec["cache_bits"] is not None:
            pts[str(op.bits)]["cache_bits"] = min(op.b_x_tilde, 7)
    spec["operating_points"] = pts
    check = {"max_logit_gap": {"limit": limit}, "min_tokens": 10,
             "min_rungs": 3}
    cell = harness.Cell(name=f"{config}.{loop}", spec=spec,
                        mix=dict(SMALL_MIX[loop]), end_to_end=[
                            {"name": "setup_s", "unit": "s"},
                            {"name": "decode_tok_per_s", "unit": "tokens/s"},
                            {"name": "itl_p95_ms", "unit": "ms"},
                            {"name": "ttft_p95_ms", "unit": "ms"}],
                        per_layer=[], check=check, chips=1)
    return cell, cfg
