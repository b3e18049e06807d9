"""Cut a chip trace down to a small test fixture.

    python3 bench/tests/fixture_tools.py <trace.xplane.pb> <out.textproto> \
        [--seconds 0.8]

Keeps, from the first harness span on, ``--seconds`` of the device planes'
op and module lines and of the harness's host spans, as an XSpace text
proto that ``jax.profiler.ProfileData.from_text_proto`` reads back. String
stats are cut to 160 characters. Also writes <out>.expected.json: the
window, busy time, per-op and per-module sums and the longest idle gaps,
computed here directly from the kept events (independently of
bench/trace_reduce.py), for test_trace_reduce.py to compare with.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import trace_reduce  # noqa: E402

KEEP_LINES = (trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE)


def _q(s: str) -> str:
    return json.dumps(s)


def cut(pd, seconds: float):
    spans, device = [], {}
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in trace_reduce.SPAN_NAMES:
                    spans.append((ev.name, ev.start_ns, ev.end_ns))
    t0 = min(s[1] for s in spans)
    t1 = t0 + seconds * 1e9
    spans = [s for s in spans if s[1] >= t0 and s[2] <= t1]
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:") or "#" in plane.name:
            continue
        lines = {}
        for line in plane.lines:
            if line.name in KEEP_LINES:
                evs = []
                for ev in line.events:
                    if ev.start_ns >= t0 and ev.end_ns <= t1:
                        stats = [(k, v[:160]) for k, v in ev.stats
                                 if isinstance(v, str)
                                 and k in ("long_name", "hlo_category",
                                           "tf_op", "source")]
                        evs.append((ev.name, ev.start_ns, ev.end_ns, stats))
                lines[line.name] = evs
        device[plane.name] = lines
    return device, spans


def to_text(device: dict, spans: list) -> str:
    out = []
    pid = 0
    base = min(s[1] for s in spans)
    for pname, lines in list(device.items()) + [("/host:CPU", None)]:
        pid += 1
        names: dict = {}
        stat_names: dict = {}
        body = []
        items = (lines.items() if lines is not None
                 else [("python", [(n, a, b, []) for n, a, b in spans])])
        for li, (lname, evs) in enumerate(items):
            body.append(f"  lines {{ id: {li + 1} name: {_q(lname)} "
                        f"timestamp_ns: {int(base)}")
            for name, a, b, stats in evs:
                mid = names.setdefault(name, len(names) + 1)
                st = " ".join(
                    f"stats {{ metadata_id: "
                    f"{stat_names.setdefault(k, len(stat_names) + 1)} "
                    f"str_value: {_q(v)} }}" for k, v in stats)
                body.append(f"    events {{ metadata_id: {mid} offset_ps: "
                            f"{int(round((a - base) * 1000))} duration_ps: "
                            f"{int(round((b - a) * 1000))} {st} }}")
            body.append("  }")
        out.append(f"planes {{\n  id: {pid}\n  name: {_q(pname)}")
        out.extend(body)
        for name, mid in names.items():
            out.append(f"  event_metadata {{ key: {mid} value {{ id: {mid} "
                       f"name: {_q(name)} }} }}")
        for name, sid in stat_names.items():
            out.append(f"  stat_metadata {{ key: {sid} value {{ id: {sid} "
                       f"name: {_q(name)} }} }}")
        out.append("}")
    return "\n".join(out) + "\n"


def expected(pd) -> dict:
    """The numbers of a (cut) trace, computed directly from its events."""
    import numpy as np
    spans, busy, ops, mods, gaps = [], [], {}, {}, []
    mod_n: dict = {}
    dev_ops = []
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                if plane.name.startswith("/device:TPU:"):
                    if line.name == trace_reduce.OPS_LINE:
                        dev_ops.append((ev.start_ns, ev.end_ns))
                        ops[ev.name] = ops.get(ev.name, 0.0) + (
                            ev.end_ns - ev.start_ns) * 1e-9
                    elif line.name == trace_reduce.MODULES_LINE:
                        mods[ev.name] = mods.get(ev.name, 0.0) + (
                            ev.end_ns - ev.start_ns) * 1e-9
                        mod_n[ev.name] = mod_n.get(ev.name, 0) + 1
                elif ev.name in trace_reduce.SPAN_NAMES:
                    spans.append((ev.start_ns, ev.end_ns))
    w0 = min(a for a, _ in spans)
    w1 = max(b for _, b in spans)
    # busy: mark every nanosecond-bucket (1 us) covered by an op
    grid = np.zeros(int((w1 - w0) / 1000) + 1, bool)
    for a, b in dev_ops:
        lo = max(int((a - w0) / 1000), 0)
        hi = min(int((b - w0) / 1000), grid.size)
        grid[lo:hi] = True
    return {"window_s": (w1 - w0) * 1e-9,
            "busy_s": float(grid.sum()) * 1e-6,
            "ops": ops, "modules": mods, "module_counts": mod_n}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("trace")
    ap.add_argument("out")
    ap.add_argument("--seconds", type=float, default=0.8)
    args = ap.parse_args()
    from jax.profiler import ProfileData
    device, spans = cut(ProfileData.from_file(args.trace), args.seconds)
    text = to_text(device, spans)
    with open(args.out, "w") as f:
        f.write(text)
    exp = expected(ProfileData.from_text_proto(text))
    with open(args.out + ".expected.json", "w") as f:
        json.dump(exp, f, indent=1, sort_keys=True)
    print(f"{args.out}: {len(text)} bytes", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
