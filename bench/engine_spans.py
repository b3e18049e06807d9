"""The serving engine's own spans and device scopes in a profiler trace.

    python3 bench/engine_spans.py <trace_dir>

``ServeEngine`` writes host spans named ``serve.*`` (``serve.next_wave``,
``serve.prefill`` with ``serve.init_state``, ``serve.step``, and under
either ``serve.dispatch`` and ``serve.sample``), each carrying its wave's
``seq`` and ``rung``; its jitted step is the module ``jit_decode_step``,
and device scopes inside it are named ``ssm_state`` and ``act_range``.

This prints one JSON object: over the window bench/trace_reduce.py sets
(the harness's spans, or the engine's own where no harness ran), per span
name its time and count, the decode-step dispatch time, the device idle
time inside the union of ``serve.*`` spans, and each scope's share of the
step's device time. A TPU trace keeps an op's scope path (its ``tf_op``)
in the op's event metadata, which ``jax.profiler.ProfileData`` does not
show, so the trace is read here as the XSpace protobuf it is. The harness
does not call this yet; a trace kept with ``bench/run.py --keep-trace`` or
taken with ``jax.profiler.trace`` around ``ServeEngine.generate`` is read
by hand.
"""
from __future__ import annotations

import json
import os
import sys
from collections import defaultdict

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import trace_reduce  # noqa: E402

PREFIX = "serve."
STEP_MODULE = "jit_decode_step"
SCOPES = ("ssm_state", "act_range")

# The XSpace messages (tsl/profiler/protobuf/xplane.proto), the fields read
# here; a map field is declared as its wire form, repeated {key, value}.
_FIELDS = {
    "XSpace": [("planes", 1, "XPlane", True)],
    "XPlane": [("id", 1, "int64", False), ("name", 2, "string", False),
               ("lines", 3, "XLine", True),
               ("event_metadata", 4, "EventMetadataEntry", True),
               ("stat_metadata", 5, "StatMetadataEntry", True)],
    "XLine": [("name", 2, "string", False),
              ("timestamp_ns", 3, "int64", False),
              ("events", 4, "XEvent", True)],
    "XEvent": [("metadata_id", 1, "int64", False),
               ("offset_ps", 2, "int64", False),
               ("duration_ps", 3, "int64", False)],
    "XStat": [("metadata_id", 1, "int64", False),
              ("str_value", 5, "string", False),
              ("ref_value", 7, "uint64", False)],
    "XEventMetadata": [("name", 2, "string", False),
                       ("stats", 5, "XStat", True)],
    "XStatMetadata": [("name", 2, "string", False)],
    "EventMetadataEntry": [("key", 1, "int64", False),
                           ("value", 2, "XEventMetadata", False)],
    "StatMetadataEntry": [("key", 1, "int64", False),
                          ("value", 2, "XStatMetadata", False)],
}
_XSPACE = None


def _xspace_class():
    global _XSPACE
    if _XSPACE is None:
        from google.protobuf import descriptor_pb2, descriptor_pool
        from google.protobuf import message_factory
        fd = descriptor_pb2.FileDescriptorProto(
            name="bench_engine_spans_xplane.proto", package="bench_xplane",
            syntax="proto3")
        scalar = {"int64": 3, "uint64": 4, "string": 9}
        for msg, fields in _FIELDS.items():
            m = fd.message_type.add(name=msg)
            for name, num, typ, rep in fields:
                f = m.field.add(name=name, number=num, label=3 if rep else 1)
                if typ in scalar:
                    f.type = scalar[typ]
                else:
                    f.type, f.type_name = 11, ".bench_xplane." + typ
        pool = descriptor_pool.DescriptorPool()
        pool.Add(fd)
        _XSPACE = message_factory.GetMessageClass(
            pool.FindMessageTypeByName("bench_xplane.XSpace"))
    return _XSPACE


def parse(data: bytes):
    """An XSpace from its serialized bytes (an .xplane.pb file, or
    ``ProfileData.text_proto_to_serialized_xspace`` of a text proto)."""
    xs = _xspace_class()()
    xs.ParseFromString(data)
    return xs


def _tf_op(meta, stat_names: dict) -> str:
    for st in meta.stats:
        if stat_names.get(st.metadata_id) == "tf_op":
            return st.str_value or stat_names.get(st.ref_value, "")
    return ""


def collect(xs) -> dict:
    """The events this reads, in ns on the trace's clock: per device its
    ops [(name, start, end, tf_op)] and modules [(name, start, end)]; the
    harness's spans and the engine's spans [(name, start, end)]. An engine
    span's name is taken before any ``#``."""
    devices, harness, engine = [], [], []
    for plane in xs.planes:
        names = {e.key: e.value.name for e in plane.event_metadata}
        if plane.name.startswith("/device:TPU:") and "#" not in plane.name:
            stat_names = {e.key: e.value.name for e in plane.stat_metadata}
            tf_op = {e.key: _tf_op(e.value, stat_names)
                     for e in plane.event_metadata}
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                base = line.timestamp_ns * 1000
                evs = [((base + ev.offset_ps) / 1000,
                        (base + ev.offset_ps + ev.duration_ps) / 1000,
                        ev.metadata_id) for ev in line.events]
                if line.name == trace_reduce.OPS_LINE:
                    dev["ops"] = [(names.get(m, ""), a, b, tf_op.get(m, ""))
                                  for a, b, m in evs]
                elif line.name == trace_reduce.MODULES_LINE:
                    dev["modules"] = [(names.get(m, ""), a, b)
                                      for a, b, m in evs]
            if dev["ops"]:
                devices.append(dev)
            continue
        for line in plane.lines:
            base = line.timestamp_ns * 1000
            for ev in line.events:
                name = names.get(ev.metadata_id, "")
                a = (base + ev.offset_ps) / 1000
                b = (base + ev.offset_ps + ev.duration_ps) / 1000
                if name.split("#", 1)[0].startswith(PREFIX):
                    engine.append((name.split("#", 1)[0], a, b))
                elif name in trace_reduce.SPAN_NAMES:
                    harness.append((name, a, b))
    return {"devices": devices, "harness": harness, "engine": engine}


def _clip(intervals, w0: float, w1: float) -> list:
    return [(max(a, w0), min(b, w1)) for a, b in intervals
            if b > w0 and a < w1]


def _overlap(a: list, b: list) -> float:
    """Total length of the intersection of two sorted disjoint lists."""
    i = j = 0
    out = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def summarize(ev: dict) -> dict:
    """The engine's numbers over the harness's window (over the engine's
    spans where no harness ran), device numbers averaged over devices."""
    devices, engine = ev["devices"], ev["engine"]
    bounds = ev["harness"] or engine
    w0 = min(s[1] for s in bounds)
    w1 = max(s[2] for s in bounds)
    spans: dict = defaultdict(lambda: {"s": 0.0, "n": 0})
    for name, a, b in engine:
        if b > w0 and a < w1:
            spans[name]["s"] += (min(b, w1) - max(a, w0)) * 1e-9
            spans[name]["n"] += 1
    steps = [(a, b) for name, a, b in engine if name == "serve.step"]
    step_dispatch = [b - a for name, a, b in engine
                     if name == "serve.dispatch" and w0 <= a and b <= w1
                     and any(s <= a and b <= e for s, e in steps)]
    covered = trace_reduce._union(_clip([(a, b) for _, a, b in engine],
                                        w0, w1))
    n_dev = max(len(devices), 1)
    idle = 0.0
    step = {"s": 0.0, "n": 0.0}
    scopes = {sc: {"s": 0.0, "n": 0.0} for sc in SCOPES}
    for dev in devices:
        busy = trace_reduce._union(_clip([(a, b) for _, a, b, _ in
                                          dev["ops"]], w0, w1))
        idle += _overlap(covered, [[w0, w1]]) - _overlap(covered, busy)
        for name, a, b in dev["modules"]:
            if b > w0 and a < w1 and name.split("(", 1)[0] == STEP_MODULE:
                step["s"] += (b - a) * 1e-9 / n_dev
                step["n"] += 1 / n_dev
        for _, a, b, tf_op in dev["ops"]:
            if b > w0 and a < w1:
                for sc in SCOPES:
                    if sc in tf_op:
                        scopes[sc]["s"] += (b - a) * 1e-9 / n_dev
                        scopes[sc]["n"] += 1 / n_dev
    return {
        "window_s": (w1 - w0) * 1e-9,
        "n_devices": len(devices),
        "spans": dict(spans),
        "step_dispatch": {"s": sum(step_dispatch) * 1e-9,
                          "n": len(step_dispatch)},
        "engine_idle_s": idle * 1e-9 / n_dev,
        "step_module": step,
        "scopes": scopes,
    }


def readings(eng: dict) -> dict:
    """The per-layer numbers the engine's names give (None where the
    trace holds nothing to read)."""
    out = {}
    d = eng["step_dispatch"]
    out["step_dispatch_ms"] = 1e3 * d["s"] / d["n"] if d["n"] else None
    out["engine_idle_share"] = (
        100.0 * eng["engine_idle_s"] / eng["window_s"]
        if eng["spans"] and eng["n_devices"] else None)
    step_s = eng["step_module"]["s"]
    for sc in SCOPES:
        v = eng["scopes"][sc]
        out[sc + "_share"] = (100.0 * v["s"] / step_s
                              if v["n"] and step_s > 0 else None)
    return out


def reduce_bytes(data: bytes) -> dict:
    return summarize(collect(parse(data)))


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    with open(trace_reduce.find_xplane(argv[0]), "rb") as f:
        eng = reduce_bytes(f.read())
    print(json.dumps({"engine": eng, "readings": readings(eng)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
