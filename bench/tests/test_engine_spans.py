"""The engine's spans and scopes in a trace (bench/engine_spans.py).

The synthetic trace of test_trace_reduce.py, extended with nested
``serve.*`` spans, checks the new numbers against hand-computed values
and shows the existing reduction reading the extended trace exactly as
the original; a small trace with ``tf_op`` in its event metadata checks
the scope shares. The chip fixture (fixtures/, cut by fixture_serve.py)
checks them against the count in its .expected.json."""
import glob
import json
import os
import re

import time

import jax
import pytest
from jax.profiler import ProfileData

from bench import engine_spans, harness, trace_reduce
from bench.tests import test_trace_reduce as TR
from bench.tests.util import reduced_cell

ROOT = os.path.dirname(harness.BENCH_DIR)

# serve.* spans on a thread of their own, in microseconds: a decode step
# (dispatch, sample), wave formation (its arguments encoded in the name,
# as a TraceMe may write them), a prefill (init_state, dispatch, sample)
ENGINE_SPANS = [("serve.step", 0.2, 4.4), ("serve.dispatch", 0.5, 1.5),
                ("serve.sample", 3.5, 4.2),
                ("serve.next_wave#wave=2,rung=4#", 4.6, 4.9),
                ("serve.prefill", 5.0, 9.0), ("serve.init_state", 5.0, 5.5),
                ("serve.dispatch", 5.5, 6.5), ("serve.sample", 8.5, 9.0)]


def _extended() -> str:
    names = list(dict.fromkeys(n for n, _, _ in ENGINE_SPANS))
    events = "\n".join(
        f"    events {{ metadata_id: {names.index(n) + 3} "
        f"offset_ps: {round(a * 1e6)} duration_ps: {round((b - a) * 1e6)} }}"
        for n, a, b in ENGINE_SPANS)
    meta = "\n".join(
        f'  event_metadata {{ key: {i + 3} value {{ id: {i + 3} '
        f'name: "{n}" }} }}' for i, n in enumerate(names))
    line = (f'  lines {{ id: 2 name: "engine" timestamp_ns: 0\n{events}\n'
            f'  }}\n{meta}\n')
    head, tail = TR.SYNTH.rsplit('  event_metadata { key: 1 value { id: 1 '
                                 'name: "step_lane" } }', 1)
    return (head + line + '  event_metadata { key: 1 value { id: 1 '
            'name: "step_lane" } }' + tail)


EXTENDED = _extended()


def test_existing_reduction_reads_the_extended_trace_unchanged():
    old = trace_reduce.reduce_profile(ProfileData.from_text_proto(TR.SYNTH))
    new = trace_reduce.reduce_profile(ProfileData.from_text_proto(EXTENDED))
    assert new == old
    assert [g[0] for g in new["breakdown"]["idle_gaps"]] == \
        ["arrival_wait", "arrival_wait", "step_lane"]


def _reduce(text: str) -> dict:
    return engine_spans.reduce_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))


def test_engine_numbers_on_the_synthetic_trace():
    eng = _reduce(EXTENDED)
    us = pytest.approx
    assert eng["window_s"] == us(10e-6)
    want = {"serve.step": (4.2, 1), "serve.dispatch": (2.0, 2),
            "serve.sample": (1.2, 2), "serve.next_wave": (0.3, 1),
            "serve.prefill": (4.0, 1), "serve.init_state": (0.5, 1)}
    assert set(eng["spans"]) == set(want)
    for name, (s, n) in want.items():
        assert eng["spans"][name]["s"] == us(s * 1e-6), name
        assert eng["spans"][name]["n"] == n, name
    # only the dispatch inside serve.step is a decode-step dispatch
    assert eng["step_dispatch"]["n"] == 1
    assert eng["step_dispatch"]["s"] == us(1e-6)
    # serve spans cover 8.5 us, of which the device is busy 3 + 2 us
    assert eng["engine_idle_s"] == us(3.5e-6)
    out = engine_spans.readings(eng)
    assert out["step_dispatch_ms"] == us(1e-3)
    assert out["engine_idle_share"] == us(35.0)
    red = trace_reduce.reduce_profile(ProfileData.from_text_proto(EXTENDED))
    assert out["engine_idle_share"] <= \
        harness.load_reader("device_idle_share")({"trace": red})
    # no jit_decode_step module here, so no scope share
    assert out["ssm_state_share"] is None and out["act_range_share"] is None


# ops of the step named by their scope in the event metadata (one by a
# reference to a stat name, as a trace may store a repeated string), an
# eager module's op outside the step
SCOPED = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 3000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 4000000 duration_ps: 6000000 }
    events { metadata_id: 5 offset_ps: 12000000 duration_ps: 1000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 6 offset_ps: 12000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[4] fusion()"
    stats { metadata_id: 1
            str_value: "jit(decode_step)/closed_call/ssm_state/add:" } } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2 = f32[] fusion()"
    stats { metadata_id: 1 ref_value: 2 } } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.3 = f32[4] fusion()"
    stats { metadata_id: 1 str_value: "jit(decode_step)/mul:" } } }
  event_metadata { key: 4 value { id: 4 name: "jit_decode_step(42)" } }
  event_metadata { key: 5 value { id: 5 name: "%reduce.1 = s32[] reduce()" } }
  event_metadata { key: 6 value { id: 6 name: "jit__argmax(7)" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 2 value { id: 2
    name: "jit(decode_step)/closed_call/act_range/reduce_max:" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 14000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "step_lane" } }
}
"""


def test_scope_shares_divide_by_the_step_module():
    eng = _reduce(SCOPED)
    assert eng["step_module"] == {"s": pytest.approx(10e-6), "n": 1}
    assert eng["scopes"]["ssm_state"] == {"s": pytest.approx(3e-6), "n": 1}
    assert eng["scopes"]["act_range"] == {"s": pytest.approx(1e-6), "n": 1}
    out = engine_spans.readings(eng)
    assert out["ssm_state_share"] == pytest.approx(30.0)
    assert out["act_range_share"] == pytest.approx(10.0)
    # no engine spans: nothing to say about dispatch or idle inside them
    assert out["step_dispatch_ms"] is None
    assert out["engine_idle_share"] is None


def test_live_rows_count_the_harness_s_useful_rows():
    """The engine's live_rows and decode_steps counters move, over a
    window, exactly as the harness's own count of useful rows and decode
    steps (rows_per_step), so live_rows_per_step reads the same rows."""
    cell, cfg = reduced_cell("zamba2-1.2b", limit=0.1)
    engines, before = [], {}

    def keep(eng):
        engines.append(eng)
        before.update(eng.describe())
    got = harness.serve(cell, 2**31 + 5, 2.0, False, time.perf_counter(),
                        cfg=cfg, devs=jax.devices()[:1], patch=keep)
    win, after = got["window"], engines[0].describe()
    assert win.decode_steps > 0
    assert after["decode_steps"] - before["decode_steps"] == win.decode_steps
    assert after["live_rows"] - before["live_rows"] == win.useful_rows
    assert after["prefill_steps"] - before["prefill_steps"] == \
        win.prefill_steps


def _program_names() -> set:
    """Every span and scope name the program writes into a trace."""
    names = {"jit_decode_step"}
    rx = re.compile(r'(?:_span|TraceAnnotation|named_scope)\(\s*"([^"]+)"')
    for path in glob.glob(os.path.join(ROOT, "src", "**", "*.py"),
                          recursive=True):
        with open(path) as f:
            names.update(rx.findall(f.read()))
    return names


def test_program_names_stay_clear_of_the_harness_and_the_readers():
    names = _program_names()
    assert {"serve.step", "serve.dispatch", "ssm_state",
            "act_range"} <= names
    patterns = []
    for path in glob.glob(os.path.join(harness.BENCH_DIR, "metrics",
                                       "*.py")):
        mod = harness.load_reader_module(
            os.path.splitext(os.path.basename(path))[0])
        if hasattr(mod, "KERNEL"):
            patterns.append(mod.KERNEL)
    assert patterns
    for name in names:
        assert name not in trace_reduce.SPAN_NAMES, name
        for p in patterns:
            assert not re.search(p, name), (name, p)


@TR.needs_fixture
def test_engine_numbers_on_the_chip_trace():
    with open(TR.FIXTURE) as f:
        eng = _reduce(f.read())
    with open(TR.FIXTURE + ".expected.json") as f:
        exp = json.load(f)["engine"]
    assert exp["steps"] > 0 and exp["step_dispatch"]["n"] > 0
    assert eng["spans"]["serve.step"]["n"] == exp["steps"]
    assert eng["step_dispatch"]["n"] == exp["step_dispatch"]["n"]
    assert eng["step_dispatch"]["s"] == pytest.approx(
        exp["step_dispatch"]["s"], rel=1e-6)
    assert eng["engine_idle_s"] == pytest.approx(exp["engine_idle_s"],
                                                 rel=1e-6)
    for sc in engine_spans.SCOPES:
        assert exp["scopes"][sc]["n"] > 0, sc
        assert eng["scopes"][sc]["n"] == exp["scopes"][sc]["n"], sc
        assert eng["scopes"][sc]["s"] == pytest.approx(
            exp["scopes"][sc]["s"], rel=1e-6), sc
    # the step module is the one the existing step reader picks
    red = trace_reduce.reduce_profile(ProfileData.from_text_proto(
        open(TR.FIXTURE).read()))
    name, v = max(red["modules"].items(), key=lambda kv: kv[1]["s"])
    assert name.startswith(engine_spans.STEP_MODULE + "(")
    assert eng["step_module"]["s"] == pytest.approx(v["s"], rel=1e-6)
