"""Reduce a profiler trace (.xplane.pb) to what the per-layer readers need.

Read with ``jax.profiler.ProfileData`` alone. Device planes are the
``/device:TPU:<n>`` planes; on each, the "XLA Ops" line holds one event per
operation the chip ran and the "XLA Modules" line one event per executable
run. The harness's host spans (TraceAnnotations named in SPAN_NAMES) set the
traced window and name what the host was doing in each idle gap.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

SPAN_NAMES = ("next_wave", "prefill_wave", "step_lane", "token_fetch",
              "arrival_wait")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def _stat_text(ev) -> str:
    return " ".join(f"{k}={v}" for k, v in ev.stats if isinstance(v, str))


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def find_xplane(trace_dir: str) -> str:
    hits = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(hits, key=os.path.getmtime)


def reduce_dir(trace_dir: str) -> dict:
    return reduce_profile(load(find_xplane(trace_dir)))


def reduce_profile(pd) -> dict:
    """The reduction: per device, op and module events; host spans; the
    window, busy time, idle gaps and the breakdown the result line keeps."""
    spans = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "#" not in plane.name:
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(ev.name, ev.start_ns, ev.end_ns, _stat_text(ev))
                           for ev in line.events]
                elif line.name == MODULES_LINE:
                    modules = [(ev.name, ev.start_ns, ev.end_ns)
                               for ev in line.events]
            if ops or modules:
                devices.append({"name": plane.name, "ops": ops,
                                "modules": modules})
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in SPAN_NAMES:
                        spans.append((ev.name, ev.start_ns, ev.end_ns))
    return summarize(devices, spans)


def summarize(devices: list, spans: list) -> dict:
    """The numbers of a reduced trace. ``devices``: [{"name", "ops":
    [(name, start_ns, end_ns, text)], "modules": [(name, start, end)]}];
    ``spans``: [(name, start_ns, end_ns)] of the harness."""
    spans = sorted(spans, key=lambda s: s[1])
    if spans:
        w0, w1 = spans[0][1], max(s[2] for s in spans)
    else:
        allev = [(e[1], e[2]) for d in devices for e in d["ops"]]
        w0, w1 = min(a for a, _ in allev), max(b for _, b in allev)
    window_s = (w1 - w0) * 1e-9
    busy, gaps = [], []
    op_time: dict = defaultdict(float)
    op_count: dict = defaultdict(int)
    op_text: dict = {}
    mod_time: dict = defaultdict(float)
    mod_count: dict = defaultdict(int)
    for d in devices:
        iv = [(max(s, w0), min(e, w1)) for _, s, e, _ in d["ops"]
              if e > w0 and s < w1]
        u = _union(iv)
        busy.append(sum(e - s for s, e in u) * 1e-9)
        prev = w0
        for s, e in u:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        if w1 > prev:
            gaps.append((prev, w1))
        for name, s, e, text in d["ops"]:
            if e > w0 and s < w1:
                op_time[name] += (e - s) * 1e-9
                op_count[name] += 1
                op_text.setdefault(name, text)
        for name, s, e in d["modules"]:
            if e > w0 and s < w1:
                mod_time[name] += (e - s) * 1e-9
                mod_count[name] += 1
    n_dev = max(len(devices), 1)
    labelled = [(label_gap(g, spans), (g[1] - g[0]) * 1e-9) for g in gaps]
    idle_by_span: dict = defaultdict(float)
    for name, sec in labelled:
        idle_by_span[name] += sec / n_dev
    top_ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    top_gaps = sorted(labelled, key=lambda kv: -kv[1])[:10]
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / n_dev,
        "n_devices": len(devices),
        "ops": {k: {"s": op_time[k] / n_dev, "n": op_count[k] / n_dev,
                    "text": op_text.get(k, "")} for k in op_time},
        "modules": {k: {"s": mod_time[k] / n_dev,
                        "n": mod_count[k] / n_dev} for k in mod_time},
        "idle_by_span": dict(idle_by_span),
        "breakdown": {"device_ops": [[k, v / n_dev] for k, v in top_ops],
                      "idle_gaps": [[k, v] for k, v in top_gaps]},
    }


def label_gap(gap: tuple, spans: list) -> str:
    """The harness span that covers most of an idle gap ("untraced" when
    the host was outside every span)."""
    s, e = gap
    cover: dict = defaultdict(int)
    for name, a, b in spans:
        if b <= s:
            continue
        if a >= e:
            break
        cover[name] += min(b, e) - max(a, s)
    if not cover:
        return "untraced"
    return max(cover.items(), key=lambda kv: kv[1])[0]


def kernel_events(red: dict, pattern: str) -> dict:
    """Ops whose name or stat text matches ``pattern``: {"s", "n"}."""
    rx = re.compile(pattern)
    s = n = 0.0
    for name, v in red["ops"].items():
        if rx.search(name) or rx.search(v.get("text", "")):
            s += v["s"]
            n += v["n"]
    return {"s": s, "n": n}
