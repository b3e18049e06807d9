"""The trace reduction and the per-layer readers.

A synthetic trace checks the arithmetic exactly; the fixture cut from a
chip trace (fixtures/, made by fixture_tools.py) checks that the reduction
reads a real trace as the independent count in its .expected.json does."""
import json
import os
import types

import pytest
from jax.profiler import ProfileData

from bench import flops, harness, peaks, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "fixtures", "zamba2_decode.textproto")

SYNTH = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000
             stats { metadata_id: 1 str_value: "pann_matmul_packed_act" } }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 6000000 duration_ps: 2000000
             stats { metadata_id: 1 str_value: "pann_matmul_packed_act" } }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 1000000 duration_ps: 3000000 }
    events { metadata_id: 3 offset_ps: 6000000 duration_ps: 2000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "custom-call.1" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit_step" } }
  stat_metadata { key: 1 value { id: 1 name: "long_name" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4500000 }
    events { metadata_id: 2 offset_ps: 4500000 duration_ps: 5500000 }
  }
  event_metadata { key: 1 value { id: 1 name: "step_lane" } }
  event_metadata { key: 2 value { id: 2 name: "arrival_wait" } }
}
"""


def synth():
    return trace_reduce.reduce_profile(ProfileData.from_text_proto(SYNTH))


def test_window_busy_and_gaps():
    red = synth()
    assert red["window_s"] == pytest.approx(10e-6)
    assert red["busy_s"] == pytest.approx(5e-6)
    assert red["n_devices"] == 1
    assert red["idle_by_span"]["arrival_wait"] == pytest.approx(4e-6)
    assert red["idle_by_span"]["step_lane"] == pytest.approx(1e-6)
    gaps = red["breakdown"]["idle_gaps"]
    assert gaps[0][0] == "arrival_wait" and gaps[0][1] == pytest.approx(2e-6)
    ops = dict(red["breakdown"]["device_ops"])
    assert ops["custom-call.1"] == pytest.approx(4e-6)


def test_readers_on_the_synthetic_trace():
    red = synth()
    assert harness.load_reader("device_idle_share")({"trace": red}) == \
        pytest.approx(50.0)
    assert harness.load_reader("decode_step_ms")({"trace": red}) == \
        pytest.approx(2.5e-3)
    ev = trace_reduce.kernel_events(red, r"pann_matmul_packed_act")
    assert ev == {"s": pytest.approx(4e-6), "n": 2}


def test_packed_roofline_counts_the_algorithm_work():
    spec = harness.load_cell("zamba2-1.2b.decode").spec
    pk = peaks.peaks("TPU v5 lite")
    bits = {(k, n): 6 for k, n, _ in flops.projections(spec)}
    per_step = sum(c * flops.matmul_min_s(16, k, n, 6, pk)
                   for k, n, c in flops.projections(spec))
    red = {"ops": {"custom-call.1": {"s": 2 * per_step * 4, "n": 2,
                                     "text": "pann_matmul_packed_act"}}}
    ctx = {"trace": red, "spec": spec, "device_kind": "TPU v5 lite",
           "max_batch": 16, "weight_bits": {2: bits},
           "snap": {"steps0": {2: 10}, "steps1": {2: 12}}}
    assert harness.load_reader("packed_matmul_roofline")(ctx) == \
        pytest.approx(25.0)
    red["ops"] = {}
    assert harness.load_reader("packed_matmul_roofline")(ctx) is None


def test_unknown_chip_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def test_counting_readers():
    win = types.SimpleNamespace(decode_steps=10, useful_rows=55,
                                prefill_steps=30, prompt_tokens=0,
                                reqs=[], lanes=[], t0=0.0, t1=1.0)
    assert harness.load_reader("rows_per_step")({"window": win}) == 5.5
    assert harness.load_reader("prefill_step_share")({"window": win}) == 75.0
    assert harness.load_reader("decode_mfu")({"window": win}) is None


needs_fixture = pytest.mark.skipif(
    not os.path.exists(FIXTURE),
    reason="no trace fixture cut from a chip run (fixture_tools.py)")


def fixture():
    with open(FIXTURE) as f:
        pd = ProfileData.from_text_proto(f.read())
    with open(FIXTURE + ".expected.json") as f:
        exp = json.load(f)
    return trace_reduce.reduce_profile(pd), exp


@needs_fixture
def test_fixture_from_the_chip():
    red, exp = fixture()
    assert red["window_s"] == pytest.approx(exp["window_s"], rel=1e-6)
    assert red["busy_s"] == pytest.approx(exp["busy_s"], abs=2e-6 * len(
        exp["ops"]) + 1e-4)
    for name, s in exp["ops"].items():
        assert red["ops"][name]["s"] == pytest.approx(s, rel=1e-6, abs=1e-9)
    for name, s in exp["modules"].items():
        assert red["modules"][name]["s"] == pytest.approx(s, rel=1e-6)


@needs_fixture
@pytest.mark.parametrize("metric", ["packed_matmul_roofline",
                                    "decode_attention_roofline"])
def test_kernel_names_match_the_chip_trace(metric):
    """Each roofline reader's kernel pattern finds, in the chip trace, the
    events the independent count names as that kernel's."""
    red, exp = fixture()
    rx = harness.load_reader_module(metric).KERNEL
    ev = trace_reduce.kernel_events(red, rx)
    want = exp["kernels"][metric]
    assert want["n"] > 0
    assert ev["n"] == want["n"]
    assert ev["s"] == pytest.approx(want["s"], rel=1e-6)


@needs_fixture
def test_trace_readers_on_the_chip_trace():
    red, exp = fixture()
    idle = harness.load_reader("device_idle_share")({"trace": red})
    assert idle == pytest.approx(
        100.0 * (1 - exp["busy_s"] / exp["window_s"]), abs=0.05)
    step = harness.load_reader("decode_step_ms")({"trace": red})
    name, s = max(exp["modules"].items(), key=lambda kv: kv[1])
    assert step == pytest.approx(1e3 * s / exp["module_counts"][name],
                                 rel=1e-6)
