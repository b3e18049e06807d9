"""Cut a chip trace down to the test fixture, the engine's names included.

    python3 bench/tests/fixture_serve.py <trace.xplane.pb> <out.textproto> \
        [--seconds 0.3]

Keeps what fixture_tools.py keeps (from the first harness span on, up
to ``--seconds``, the device planes' op and module lines and the
harness's spans), ending with a harness span so that no op lies outside
the window, and also the ``serve.*`` host spans in that stretch and
each op's ``tf_op`` (its scope path, which a TPU trace holds in the op's
event metadata), as an XSpace text proto. The <out>.expected.json holds
fixture_tools.expected's numbers and two more groups, counted here from
the kept events, apart from the code under test: ``kernels``, per
roofline reader the events of its Pallas kernel (the custom calls the
HLO instruction name gives to that kernel), and ``engine``, what
bench/engine_spans.py reads.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import engine_spans, trace_reduce  # noqa: E402
from bench.tests import fixture_tools  # noqa: E402

# the Pallas kernel behind each roofline reader: its instruction name
KERNELS = {"packed_matmul_roofline": "%pann_matmul_packed_act.",
           "decode_attention_roofline": "%decode_attention."}


def cut(ev: dict, seconds: float) -> dict:
    """Every event inside [t0, t1]: t0 the first harness span's start, t1
    the end of the last harness span within ``seconds`` of it, so the
    whole cut is the window the reduction sets."""
    t0 = min(s[1] for s in ev["harness"])
    t1 = max(b for _, _, b in ev["harness"] if b <= t0 + seconds * 1e9)

    def keep(xs, a=1, b=2):
        return [x for x in xs if x[a] >= t0 and x[b] <= t1]
    return {"devices": [{"ops": keep(d["ops"]), "modules": keep(d["modules"])}
                        for d in ev["devices"]],
            "harness": keep(ev["harness"]), "engine": keep(ev["engine"])}


def _plane(pid: int, name: str, lines: list, base: float) -> str:
    """One plane; ``lines``: [(line name, [(event name, start, end,
    tf_op)])]."""
    q = json.dumps
    meta: dict = {}
    out = [f"planes {{\n  id: {pid}\n  name: {q(name)}"]
    for li, (lname, evs) in enumerate(lines):
        out.append(f"  lines {{ id: {li + 1} name: {q(lname)} "
                   f"timestamp_ns: {int(base)}")
        for en, a, b, tf_op in evs:
            mid = meta.setdefault(en, (len(meta) + 1, tf_op))[0]
            out.append(f"    events {{ metadata_id: {mid} offset_ps: "
                       f"{int(round((a - base) * 1000))} duration_ps: "
                       f"{int(round((b - a) * 1000))} }}")
        out.append("  }")
    for en, (mid, tf_op) in meta.items():
        st = (f" stats {{ metadata_id: 1 str_value: {q(tf_op)} }}"
              if tf_op else "")
        out.append(f"  event_metadata {{ key: {mid} value {{ id: {mid} "
                   f"name: {q(en)}{st} }} }}")
    out.append('  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }')
    out.append("}")
    return "\n".join(out)


def to_text(ev: dict) -> str:
    base = min(s[1] for s in ev["harness"])
    planes = []
    for i, d in enumerate(ev["devices"]):
        planes.append(_plane(i + 1, f"/device:TPU:{i}", [
            (trace_reduce.OPS_LINE, d["ops"]),
            (trace_reduce.MODULES_LINE,
             [(n, a, b, "") for n, a, b in d["modules"]])], base))
    spans = sorted(ev["harness"] + ev["engine"], key=lambda s: s[1])
    planes.append(_plane(len(planes) + 1, "/host:CPU", [
        ("python", [(n, a, b, "") for n, a, b in spans])], base))
    return "\n".join(planes) + "\n"


def _idle_under(busy: list, cover: list, w0: float, w1: float) -> float:
    """Time in [w0, w1] under some ``cover`` interval and no ``busy`` one,
    by one sweep over every interval's ends."""
    edges = sorted([(a, 0, 1) for a, _ in busy] + [(b, 0, -1) for _, b in busy]
                   + [(a, 1, 1) for a, _ in cover]
                   + [(b, 1, -1) for _, b in cover])
    depth = [0, 0]
    out, t = 0.0, w0
    for x, kind, step in edges:
        x = min(max(x, w0), w1)
        if depth[1] > 0 and depth[0] == 0:
            out += x - t
        t = x
        depth[kind] += step
    return out


def expected_extra(ev: dict) -> dict:
    """Counted from the kept events: per kernel and scope the op events
    overlapping the window, the decode-step dispatches, and the idle
    inside the engine's spans."""
    w0 = min(s[1] for s in ev["harness"])
    w1 = max(s[2] for s in ev["harness"])
    ops = [o for d in ev["devices"] for o in d["ops"]]
    inside = [o for o in ops if o[2] > w0 and o[1] < w1]

    def total(hits):
        return {"n": len(hits), "s": sum(b - a for _, a, b, _ in hits) * 1e-9}
    kernels = {m: total([o for o in inside if o[0].startswith(k)
                         and 'custom_call_target="tpu_custom_call"' in o[0]])
               for m, k in KERNELS.items()}
    scopes = {sc: total([o for o in inside if sc in o[3]])
              for sc in engine_spans.SCOPES}
    serve = ev["engine"]
    steps = [(a, b) for n, a, b in serve if n == "serve.step"]
    disp = [b - a for n, a, b in serve if n == "serve.dispatch"
            and w0 <= a and b <= w1
            and any(s <= a and b <= e for s, e in steps)]
    idle = _idle_under([(a, b) for _, a, b, _ in ops],
                       [(a, b) for _, a, b in serve], w0, w1)
    return {"kernels": kernels,
            "engine": {"steps": sum(1 for n, a, b in serve
                                    if n == "serve.step"
                                    and b > w0 and a < w1),
                       "step_dispatch": {"n": len(disp),
                                         "s": sum(disp) * 1e-9},
                       "engine_idle_s": idle * 1e-9,
                       "scopes": scopes}}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("trace")
    ap.add_argument("out")
    ap.add_argument("--seconds", type=float, default=0.3)
    args = ap.parse_args()
    from jax.profiler import ProfileData
    with open(args.trace, "rb") as f:
        ev = cut(engine_spans.collect(engine_spans.parse(f.read())),
                 args.seconds)
    text = to_text(ev)
    with open(args.out, "w") as f:
        f.write(text)
    exp = fixture_tools.expected(ProfileData.from_text_proto(text))
    exp.update(expected_extra(ev))
    with open(args.out + ".expected.json", "w") as f:
        json.dump(exp, f, indent=1, sort_keys=True)
    print(f"{args.out}: {len(text)} bytes", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
