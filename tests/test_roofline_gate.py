"""Off-TPU validation of the roofline CI gate (benchmarks/roofline.py).

The timing leg only runs on TPU, so the CPU CI legs would otherwise never
exercise the threshold decision itself. Here the gate's pass/fail logic is
driven with SYNTHETIC backend measurements (monkeypatched in place of the
TPU timings) so a broken floor comparison — or a nonsense GATE_THRESHOLDS
edit — fails immediately on CPU, not on the next TPU run.
"""
import math
import sys

import pytest

sys.path.insert(0, ".")  # for benchmarks.*

from benchmarks import roofline  # noqa: E402
from benchmarks.common import device_peaks  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402


def test_gate_thresholds_are_sane_floors():
    assert set(roofline.GATE_THRESHOLDS) == {"fused", "packed"}
    for backend, floor in roofline.GATE_THRESHOLDS.items():
        assert 0.0 < floor < 1.0, (backend, floor)
    # packed trades HBM bytes for VPU unpack work — its floor must sit
    # below fused's, or the docs/kernels.md rationale is stale
    assert roofline.GATE_THRESHOLDS["packed"] < roofline.GATE_THRESHOLDS["fused"]
    m, k, n = roofline.GATE_SHAPE
    assert m % 128 == 0 and k % 128 == 0 and n % 128 == 0


def test_analyze_record_synthetic_math():
    """The hand-checkable record from assert_invariants, verified term by
    term against the peaks table rather than just for finiteness."""
    pk = device_peaks("TPU v5 lite")
    rec = {
        "arch": "synthetic", "shape": "s", "mesh": "single", "n_devices": 4,
        "flops_per_device_corrected": 1e12,
        "bytes_per_device_corrected": 1e9,
        "collective_bytes_corrected": 1e8,
        "model_flops_global": 3e12,
    }
    a = roofline.analyze_record(rec)
    assert math.isclose(a["t_compute_s"], 1e12 / pk["peak_flops"])
    assert math.isclose(a["t_memory_s"], 1e9 / pk["hbm_bw"])
    assert math.isclose(a["t_collective_s"], 1e8 / pk["ici_bw"])
    assert a["dominant"] == "compute"
    assert math.isclose(a["useful_ratio"], 0.75)
    ideal = 3e12 / pk["peak_flops"] / 4
    assert math.isclose(a["roofline_fraction"], ideal / a["t_compute_s"])
    roofline.assert_invariants()  # and the bundled self-check still holds


def _synthetic_measurements(fractions):
    peaks = device_peaks()
    return {
        backend: {
            "us": 100.0,
            "achieved_int8_ops": frac * peaks["peak_int8"],
            "fraction_of_peak": frac,
        }
        for backend, frac in fractions.items()
    }


def test_gate_passes_on_synthetic_measurements_above_floor(monkeypatch):
    monkeypatch.setattr(kops, "on_tpu", lambda: True)
    meas = _synthetic_measurements(
        {b: f + 0.01 for b, f in roofline.GATE_THRESHOLDS.items()})
    monkeypatch.setattr(roofline, "_gate_measurements", lambda: meas)
    record = roofline.gate(check=True)
    assert record["failures"] == []
    assert record["measurements"] == meas


@pytest.mark.parametrize("breached", ["fused", "packed"])
def test_gate_fails_on_synthetic_measurement_below_floor(monkeypatch,
                                                         breached):
    monkeypatch.setattr(kops, "on_tpu", lambda: True)
    fractions = {b: f + 0.01 for b, f in roofline.GATE_THRESHOLDS.items()}
    fractions[breached] = roofline.GATE_THRESHOLDS[breached] - 0.01
    monkeypatch.setattr(roofline, "_gate_measurements",
                        lambda: _synthetic_measurements(fractions))
    with pytest.raises(SystemExit):
        roofline.gate(check=True)
    # without --check semantics the breach is recorded, not raised
    record = roofline.gate(check=False)
    assert len(record["failures"]) == 1 and breached in record["failures"][0]


def test_gate_off_tpu_skips_timing_but_asserts_invariants():
    record = roofline.gate(check=True)  # CPU container: must not raise
    assert "skipped" in record and record["failures"] == []
    assert record["thresholds"] == roofline.GATE_THRESHOLDS


# ---------------------------------------------------------------------------
# $REPRO_ROOFLINE_FLOORS override (docs/kernels.md "Re-measuring the
# roofline floors")
# ---------------------------------------------------------------------------

def test_floors_default_without_env(monkeypatch):
    monkeypatch.delenv(roofline.FLOORS_ENV, raising=False)
    floors = roofline.gate_thresholds()
    assert floors == roofline.GATE_THRESHOLDS
    # a fresh dict, not the module constant — callers can't mutate defaults
    assert floors is not roofline.GATE_THRESHOLDS


def test_floors_env_override_merges_over_defaults(monkeypatch):
    monkeypatch.setenv(roofline.FLOORS_ENV, '{"fused": 0.25}')
    floors = roofline.gate_thresholds()
    assert floors["fused"] == 0.25
    assert floors["packed"] == roofline.GATE_THRESHOLDS["packed"]


@pytest.mark.parametrize("bad", [
    "not json",                      # invalid JSON
    "[0.2, 0.1]",                    # not an object
    '{"fused": 0.2, "nope": 0.1}',   # unknown backend
    '{"fused": 1.5}',                # floor outside (0, 1)
    '{"fused": 0.0}',                # zero disables the gate silently
    '{"fused": "0.2"}',              # string, not a number
    '{"fused": true}',               # bool is not a fraction
])
def test_floors_env_rejects_garbage_loudly(monkeypatch, bad):
    monkeypatch.setenv(roofline.FLOORS_ENV, bad)
    with pytest.raises(SystemExit) as ei:
        roofline.gate_thresholds()
    assert roofline.FLOORS_ENV in str(ei.value)


def test_gate_enforces_overridden_floor(monkeypatch):
    """A floor raised via the env var must actually tighten the gate: a
    measurement that clears the committed default but not the override
    fails."""
    monkeypatch.setattr(kops, "on_tpu", lambda: True)
    default = roofline.GATE_THRESHOLDS["fused"]
    monkeypatch.setenv(roofline.FLOORS_ENV,
                       '{"fused": %s}' % (default + 0.10))
    fractions = {b: f + 0.01 for b, f in roofline.GATE_THRESHOLDS.items()}
    monkeypatch.setattr(roofline, "_gate_measurements",
                        lambda: _synthetic_measurements(fractions))
    record = roofline.gate(check=False)
    assert record["floors_overridden_via"] == roofline.FLOORS_ENV
    assert record["thresholds"]["fused"] == pytest.approx(default + 0.10)
    assert len(record["failures"]) == 1 and "fused" in record["failures"][0]
    with pytest.raises(SystemExit):
        roofline.gate(check=True)
    # and a loosened floor lets a below-default measurement through
    monkeypatch.setenv(roofline.FLOORS_ENV, '{"packed": 0.01}')
    fractions = {b: f + 0.01 for b, f in roofline.GATE_THRESHOLDS.items()}
    fractions["packed"] = 0.02
    record = roofline.gate(check=True)
    assert record["failures"] == []
