"""The rwkv6-finch-1.6b cell at a reduced size on the CPU: a sound run
comes out correct, a program without the ddlerp does not, the bfloat16
control reads above the reduced limit, and the two wkv readers give the
hand-computed values on a synthetic trace.

The reduced limit is test_window's 0.1: at this size the program reads 0
exactly and the bfloat16 control 0.35 and above. The chip's limit
(bench/checks/rwkv6-finch-1.6b.decode.json) is set from readings at the
cell's own size (PERF.md)."""
import time

import jax
import numpy as np
import pytest

from bench import check, harness
from bench.tests.util import reduced_cell

CONFIG = "rwkv6-finch-1.6b"
LIMIT = 0.1
SEED = 2**31 + 5


def _run(seed=SEED):
    cell, cfg = reduced_cell(CONFIG, "closed", limit=LIMIT)
    return harness.run(cell, seed, 4.0, False, time.perf_counter(), cfg=cfg,
                       devs=jax.devices()[:1])


def test_sound_run_is_correct():
    out = _run()
    assert out["correct"], out["compared"]
    assert out["compared"]["max_logit_gap"]["value"] <= LIMIT


def test_program_without_ddlerp_is_not_correct(monkeypatch):
    from repro.models import rwkv as R

    def static_shift(x, xs, p, cfg):
        xx = xs - x
        mu = p["maa_wkvrg"].astype(x.dtype)
        return {n: x + xx * mu[i] for i, n in enumerate(R.MIX_ORDER)}

    monkeypatch.setattr(R, "_ddlerp", static_shift)
    out = _run()
    assert not out["correct"], out["compared"]


@pytest.mark.parametrize("seed", [11, 2**31 + 12, 13])
def test_control_reads_above_the_limit(seed):
    cell, cfg = reduced_cell(CONFIG, "closed", limit=LIMIT)
    got = harness.serve(cell, seed, 4.0, False, time.perf_counter(),
                        cfg=cfg, devs=jax.devices()[:1])
    r = check.readings(cell.spec, seed, got["window"], got["max_len"])
    assert r["tokens"] >= 10
    assert r["program"] <= LIMIT < r["control"], r


def _ctx():
    cell, _ = reduced_cell(CONFIG, "closed")
    spec = cell.spec                      # 2 layers, d_model 64: one head
    ops = {
        "%rwkv6_wkv_decode.3 = (f32[3,1,64]{2,1,0:T(2,128)}, f32[3,64,64]"
        "{2,1,0:T(8,128)}) custom-call(%a, %b)": {"s": 1e-4, "n": 34.0,
                                                  "text": ""},
        # ops that only read the kernel's result must not count
        "%get-tuple-element.9 = f32[3,64,64]{2,1,0} get-tuple-element("
        "%rwkv6_wkv_decode.3), index=1": {"s": 5e-5, "n": 34.0, "text": ""},
        "%fusion.2 = f32[3,64]{1,0} fusion(%x)": {"s": 3e-4, "n": 34.0,
                                                  "text": ""},
    }
    mods = {"jit_decode_step(77)": {"s": 2e-3, "n": 17.0},
            "jit__argmax(5)": {"s": 1e-5, "n": 17.0}}
    return {"spec": spec, "trace": {"ops": ops, "modules": mods},
            "snap": {"steps0": {2: 10, 4: 0, 6: 5},
                     "steps1": {2: 20, 4: 7, 6: 5}},
            "max_batch": 3, "device_kind": "TPU v5 lite"}


def test_wkv_decode_roofline_reader():
    # 17 steps x 2 layers x (2 x 3 rows x 64 x 64 x 4 B state, 4 x 3 x 64
    # x 4 B r/k/v/w, 3 x 64 x 4 B o, 64 x 4 B u = 102400 B) over 819 GB/s,
    # over the kernel's 1e-4 s
    want = 100.0 * 17 * 2 * 102400 / 819e9 / 1e-4
    got = harness.load_reader("wkv_decode_roofline")(_ctx())
    np.testing.assert_allclose(got, want, rtol=1e-12)
    assert abs(got - 4.2510378510378) < 1e-9


def test_wkv_step_share_reader():
    got = harness.load_reader("wkv_step_share")(_ctx())
    np.testing.assert_allclose(got, 5.0, rtol=1e-12)


def test_readers_find_nothing_without_the_kernel():
    ctx = _ctx()
    ctx["trace"]["ops"] = {k: v for k, v in ctx["trace"]["ops"].items()
                           if not k.startswith("%rwkv6")}
    for name in ("wkv_decode_roofline", "wkv_step_share"):
        assert harness.load_reader(name)(ctx) is None
