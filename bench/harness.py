"""The benchmark harness: set-up, the measured window and the result line.

Everything that names a cell, a configuration, a traffic mix or a metric
is data under bench/: BENCHMARK.json lists the cells, bench/configs/<name>
.json holds a configuration, bench/traffic/<name>.json a traffic mix,
bench/metrics/<name>.py the reader of one per-layer metric and
bench/checks/<cell>.json the limits of the cell's correctness comparison.

The window drives ``ServeEngine`` through its public wave/lane calls, the
ones ``ServeEngine.generate`` makes: requests are admitted as they come
due (select_rung -> scheduler.submit), waves are formed
(scheduler.next_wave) and prefilled (prefill_wave), and the lanes advance
round-robin one decode step each (step_lane). Every new token is fetched
to the host after its step, as a streaming server must; that fetch
timestamps the token.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import statistics
import sys
import tempfile
import time
from typing import Any, Callable, Optional

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

# keys of a configuration file that set the program's ModelConfig
MODEL_KEYS = ("family", "num_layers", "d_model", "num_heads", "num_kv_heads",
              "head_dim", "d_ff", "vocab_size", "norm", "activation",
              "rope_theta", "ssm_state", "ssm_head_dim", "ssm_expand",
              "ssm_conv_width", "attn_period", "dtype")


class BenchError(Exception):
    """A run that cannot give a result: it exits nonzero and prints none."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Finding a cell's pieces by name
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    spec: dict            # the configuration file
    mix: dict             # the traffic file
    end_to_end: list      # metric entries of BENCHMARK.json for this cell
    per_layer: list
    check: dict           # the cell's comparison limits
    chips: int


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bm = json.load(f)
    wl = [w for w in bm["workloads"] if w["name"] == name]
    if not wl:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    wl = wl[0]
    cfg = [c for c in bm["configs"] if c["name"] == wl["config"]][0]
    with open(os.path.join(root, cfg["file"])) as f:
        spec = json.load(f)
    with open(os.path.join(root, "bench", "traffic",
                           wl["traffic"] + ".json")) as f:
        mix = json.load(f)
    with open(os.path.join(root, "bench", "checks", name + ".json")) as f:
        check = json.load(f)
    return Cell(name=name, spec=spec, mix=mix,
                end_to_end=[m for m in bm["end_to_end"] if _applies(m, name)],
                per_layer=[m for m in bm["per_layer"] if _applies(m, name)],
                check=check, chips=int(wl["chips"]))


def load_reader_module(metric: str):
    """The module bench/metrics/<metric>.py."""
    path = os.path.join(BENCH_DIR, "metrics", metric + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str) -> Callable:
    """The ``read`` function of bench/metrics/<metric>.py."""
    return load_reader_module(metric).read


def model_config(spec: dict):
    """The program's ModelConfig of the file's architecture, with every
    size and form the file states (the file is the configuration run)."""
    from repro import configs
    cfg = configs.get_config(spec["arch"])
    return dataclasses.replace(
        cfg, **{k: spec[k] for k in MODEL_KEYS if k in spec})


def max_len_of(mix: dict) -> int:
    from bench import traffic
    plen = int(traffic._quantile_draws(mix["prompt_len"], 1)[0])
    out = mix["output_len"]
    omax = int(out["max"] if out["kind"] != "fixed" else out["value"])
    return plen + omax


# ---------------------------------------------------------------------------
# Host spans
# ---------------------------------------------------------------------------

class Spans:
    """Harness spans around the calls into the engine: while a trace runs,
    each is written into the profiler's trace as a TraceAnnotation, on the
    device's clock, where the reduction names idle gaps by them."""

    def __init__(self):
        self.tracing = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        if self.tracing:
            import jax
            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield


# ---------------------------------------------------------------------------
# Request sources
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ReqRec:
    item: Any
    due: float                      # host clock, when the request was due
    rung: int = 0
    token_times: list = dataclasses.field(default_factory=list)
    lane: int = -1
    row: int = -1

    @property
    def finished(self) -> bool:
        return len(self.token_times) >= self.item.max_new_tokens


class ClosedSource:
    """`clients` callers, each sending its next request the moment the
    last token of its previous one reached the host. The requests come
    from a pool in a fixed order, and the pool repeats when it runs out,
    so every window serves the same sizes."""

    def __init__(self, mix: dict, items: list):
        self.items = items
        self.next = 0
        self.clients = int(mix["clients"])
        self.ready: list = []

    def start(self, t0: float) -> None:
        self.ready = [t0] * self.clients

    def finished(self, rec: ReqRec, t: float) -> None:
        self.ready.append(t)

    def due(self, now: float) -> list:
        out = []
        while self.ready:
            item = self.items[self.next % len(self.items)]
            if self.next >= len(self.items):
                item = dataclasses.replace(item, uid=self.next)
            out.append((item, self.ready.pop(0)))
            self.next += 1
        return out

    def next_due(self) -> Optional[float]:
        return None


class OpenSource:
    """Arrivals on a fixed schedule, whatever the server does."""

    def __init__(self, mix: dict, items: list, times: np.ndarray):
        if len(items) < len(times):
            raise BenchError("open-loop schedule longer than its items")
        self.items = items
        self.offsets = times
        self.next = 0
        self.t0 = 0.0

    def start(self, t0: float) -> None:
        self.t0 = t0

    def finished(self, rec: ReqRec, t: float) -> None:
        pass

    def due(self, now: float) -> list:
        out = []
        while (self.next < len(self.offsets)
               and self.t0 + self.offsets[self.next] <= now):
            out.append((self.items[self.next],
                        self.t0 + float(self.offsets[self.next])))
            self.next += 1
        return out

    def next_due(self) -> Optional[float]:
        if self.next < len(self.offsets):
            return self.t0 + float(self.offsets[self.next])
        return None


def make_source(mix: dict, seed: int, vocab: int):
    from bench import traffic
    if mix["loop"] == "closed":
        items = traffic.make_items(mix, seed, vocab, int(mix["pool"]))
        return ClosedSource(mix, items)
    times = traffic.open_schedule(mix, seed)
    items = traffic.make_items(mix, seed, vocab, len(times))
    return OpenSource(mix, items, times)


# ---------------------------------------------------------------------------
# The window
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class LaneRec:
    uid: int
    rung: int
    reqs: list                      # ReqRec per real row
    prompt: np.ndarray              # (max_batch, L) int32, padded rows
    tokens: list = dataclasses.field(default_factory=list)  # (max_batch,)


@dataclasses.dataclass
class Window:
    t0: float = 0.0
    t1: float = 0.0
    reqs: list = dataclasses.field(default_factory=list)
    lanes: list = dataclasses.field(default_factory=list)
    prefill_steps: int = 0
    decode_steps: int = 0
    useful_rows: int = 0            # decode-step rows that wanted their token
    prompt_tokens: int = 0          # teacher-forced prompt tokens, real rows
    lateness: list = dataclasses.field(default_factory=list)
    steps: list = dataclasses.field(default_factory=list)  # (t, rung, pos)


def serve_window(eng, source, seconds: float, max_lanes: int, spans: Spans,
                 trace_at: Optional[tuple] = None) -> Window:
    """Drive the engine for ``seconds``. ``trace_at`` = (start, stop,
    on_start, on_stop): callbacks run between steps at those offsets."""
    from repro.serve_engine.ladder import select_rung
    from repro.serve_engine.scheduler import Request
    win = Window()
    lanes: list = []                # (Lane, LaneRec)
    by_uid: dict = {}
    trace_state = 0
    now = time.perf_counter
    win.t0 = t0 = now()
    end = t0 + seconds
    source.start(t0)

    def admit():
        t = now()
        for item, due in source.due(t):
            rung = select_rung(eng.ladder, item.budget_bits)
            req = Request(uid=item.uid, prompt=item.prompt,
                          max_new_tokens=item.max_new_tokens,
                          power_budget_bits=item.budget_bits)
            eng.scheduler.submit(req, rung=rung)
            rec = ReqRec(item=item, due=due, rung=rung.bits)
            by_uid[item.uid] = rec
            win.reqs.append(rec)
            win.lateness.append(t - due)

    def record(lrec: LaneRec, tok: np.ndarray, t: float) -> int:
        lrec.tokens.append(tok)
        useful = 0
        for rec in lrec.reqs:
            if not rec.finished:
                rec.token_times.append(t)
                useful += 1
                if rec.finished:
                    source.finished(rec, t)
        return useful

    while True:
        t = now()
        if t >= end:
            break
        if trace_at is not None:
            if trace_state == 0 and t >= t0 + trace_at[0]:
                trace_at[2]()
                trace_state = 1
            elif trace_state == 1 and t >= t0 + trace_at[1]:
                trace_at[3]()
                trace_state = 2
        admit()
        while len(lanes) < max_lanes:
            with spans("next_wave"):
                wave = eng.scheduler.next_wave()
            if wave is None:
                break
            n = len(wave.requests)
            prompt = eng._pad_rows(np.stack([r.prompt for r in wave.requests]))
            lrec = LaneRec(uid=len(win.lanes), rung=wave.rung.bits,
                           reqs=[by_uid[r.uid] for r in wave.requests],
                           prompt=np.asarray(prompt, np.int32))
            for i, rec in enumerate(lrec.reqs):
                rec.lane, rec.row = lrec.uid, i
            win.lanes.append(lrec)
            t = now()
            win.steps.extend((t, lrec.rung, i) for i in range(prompt.shape[1]))
            with spans("prefill_wave"):
                lane = eng.prefill_wave(wave)
            with spans("token_fetch"):
                tok = np.asarray(lane.tok)[:, 0]
            win.prefill_steps += prompt.shape[1]
            win.prompt_tokens += n * prompt.shape[1]
            record(lrec, tok, now())
            lanes.append((lane, lrec))
            admit()
        if not lanes:
            nxt = source.next_due()
            with spans("arrival_wait"):
                wait = (end if nxt is None else min(nxt, end)) - now()
                if wait > 0:
                    time.sleep(wait)
            continue
        for pair in list(lanes):
            lane, lrec = pair
            if lane.steps_left <= 0:
                lanes.remove(pair)
                continue
            win.steps.append((now(), lrec.rung,
                              lrec.prompt.shape[1] + len(lrec.tokens) - 1))
            with spans("step_lane"):
                done = eng.step_lane(lane)
            with spans("token_fetch"):
                tok = np.asarray(lane.tok)[:, 0]
            win.decode_steps += 1
            win.useful_rows += record(lrec, tok, now())
            if done:
                lanes.remove(pair)
            if now() >= end:
                break
            admit()
    win.t1 = now()
    if trace_state == 1:
        trace_at[3]()
    return win


# ---------------------------------------------------------------------------
# End-to-end numbers of a window
# ---------------------------------------------------------------------------

def p95(values) -> float:
    return float(np.percentile(np.asarray(values, np.float64), 95))


def end_to_end(win: Window) -> dict:
    t0, t1 = win.t0, win.t1
    toks = [t for r in win.reqs for t in r.token_times if t0 <= t <= t1]
    gaps = [(b - a) for r in win.reqs
            for a, b in zip(r.token_times, r.token_times[1:])
            if t0 <= a and b <= t1]
    ttft = [((r.token_times[0] if r.token_times else t1) - r.due)
            for r in win.reqs if t0 <= r.due <= t1]
    out = {"decode_tok_per_s": len(toks) / (t1 - t0)}
    if gaps:
        out["itl_p95_ms"] = 1e3 * p95(gaps)
        out["itl_p50_ms"] = 1e3 * float(np.median(gaps))
        out["n_gaps"] = len(gaps)
    if ttft:
        out["ttft_p95_ms"] = 1e3 * p95(ttft)
        out["ttft_p50_ms"] = 1e3 * float(np.median(ttft))
        out["n_ttft"] = len(ttft)
    return out


# ---------------------------------------------------------------------------
# Set-up
# ---------------------------------------------------------------------------

def check_ladder(eng, spec: dict) -> None:
    """The engine's operating points must be the ones the file states."""
    pts = spec["operating_points"]
    cache = eng.describe()["cache_bits_by_rung"] or {}
    for op in eng.ladder:
        want = pts.get(str(op.bits))
        if want is None or float(want["r"]) != float(op.r) \
                or int(want["b_x_tilde"]) != int(op.b_x_tilde) \
                or want.get("cache_bits") != cache.get(op.bits):
            raise BenchError(
                f"rung {op.bits}: the engine plans r={op.r}, "
                f"b~x={op.b_x_tilde}, cache={cache.get(op.bits)}; the "
                f"configuration states {want}")


def setup(cfg, spec: dict, mix: dict, seed: int, t_start: float) -> tuple:
    """Weights from the seed, the weight store, the compiled step, and one
    wave through the window's host-side shapes. Returns (engine, splits)."""
    import jax
    from bench import weights
    from repro.serve_engine.engine import ServeEngine
    from repro.serve_engine.scheduler import Request, Wave
    split = {}
    t = time.perf_counter()
    params = weights.make_params(spec, seed)
    jax.block_until_ready(params)
    split["param_init_s"] = time.perf_counter() - t
    t = time.perf_counter()
    eng = ServeEngine(cfg, params=params, ladder_bits=spec["ladder_bits"],
                      max_batch=spec["max_batch"], max_len=max_len_of(mix),
                      backend=spec["backend"], cache_bits=spec["cache_bits"],
                      autotune=False)
    jax.block_until_ready(eng.weight_store)
    del params
    gc.collect()
    split["store_build_s"] = time.perf_counter() - t
    want = spec["backend"]
    got = eng.describe()["effective_backend"]
    if got != want:
        raise BenchError(f"effective backend {got!r}, configured {want!r}")
    check_ladder(eng, spec)
    t = time.perf_counter()
    eng.warmup()
    # one short wave through every host-side shape the window uses
    from bench import traffic
    plen = int(traffic._quantile_draws(mix["prompt_len"], 1)[0])
    req = Request(uid=-1, prompt=np.zeros(plen, np.int32), max_new_tokens=3)
    lane = eng.prefill_wave(Wave(rung=eng.ladder[0], requests=(req,)))
    np.asarray(lane.tok)
    while not eng.step_lane(lane):
        np.asarray(lane.tok)
    lane.generated_rows()
    eng.steps_by_rung = {b: 0 for b in eng.steps_by_rung}
    eng.rung_switches = 0
    split["warmup_s"] = time.perf_counter() - t
    split["setup_s"] = time.perf_counter() - t_start
    return eng, split


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def device_info(devs) -> dict:
    peak = 0
    for d in devs:
        try:
            peak = max(peak, int(d.memory_stats()["peak_bytes_in_use"]))
        except Exception:
            pass
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


class CompileCounter:
    """Counts XLA compilations (every jit, eager op and program)."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def serve(cell: Cell, seed: int, seconds: float, trace: bool,
          t_start: float, cfg=None, devs=None, patch=None,
          keep_trace: Optional[str] = None) -> dict:
    """Set-up and the window of one run, with the engine freed at the end.
    ``cfg`` replaces the program configuration (tests run a reduced one);
    ``patch(engine)`` runs after set-up (tests break the served path);
    ``keep_trace`` is a directory the raw profiler trace is moved to."""
    import jax
    spec, mix = cell.spec, cell.mix
    cfg = cfg if cfg is not None else model_config(spec)
    devs = devs if devs is not None else jax.devices()[:cell.chips]
    compiles = CompileCounter()
    eng, split = setup(cfg, spec, mix, seed, t_start)
    log("setup " + json.dumps(split))
    if patch is not None:
        patch(eng)
    source = make_source(mix, seed, int(spec["vocab_size"]))
    spans = Spans()
    trace_at, tdir, snap = None, None, {}
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        tr_len = min(float(mix.get("trace_seconds", seconds)), seconds)
        start = max(0.0, (seconds - tr_len) / 2.0)

        def on_start():
            jax.profiler.start_trace(tdir)
            spans.tracing = True
            snap["steps0"] = dict(eng.steps_by_rung)
            snap["t0"] = time.perf_counter()

        def on_stop():
            snap["t1"] = time.perf_counter()
            snap["steps1"] = dict(eng.steps_by_rung)
            spans.tracing = False
            jax.profiler.stop_trace()

        trace_at = (start, start + tr_len, on_start, on_stop)
    n_before = compiles.n
    win = serve_window(eng, source, seconds, int(spec["max_lanes"]), spans,
                       trace_at)
    in_window = compiles.n - n_before
    e2e = end_to_end(win)
    e2e["setup_s"] = split["setup_s"]
    dev = device_info(devs)
    eng.assert_no_recompile()
    log(f"compilations in window: {in_window}")
    if in_window:
        raise BenchError(f"{in_window} compilation(s) inside the window")
    log("steps_by_rung " + json.dumps(eng.steps_by_rung)
        + f" rung_switches {eng.rung_switches}")
    tokens = sum(len(r.token_times) for r in win.reqs)
    flips = 0.0
    for r in win.reqs:
        led = eng.ledger_for(eng.rungs[r.rung], r.item.prompt.shape[0]
                             + r.item.max_new_tokens)
        flips += led.bitflips_per_token * len(r.token_times)
    log(f"gbitflips_per_generated_token {flips / max(tokens, 1) / 1e9}")
    due = [r for r in win.reqs if win.t0 <= r.due <= win.t1]
    log(f"requests due {len(due)} finished "
        f"{sum(r.finished for r in win.reqs)} tokens {tokens} "
        f"prefill_steps {win.prefill_steps} decode_steps {win.decode_steps}")
    if win.lateness:
        log(f"generator lateness mean_ms {1e3 * statistics.mean(win.lateness)}"
            f" max_ms {1e3 * max(win.lateness)}")
    log("end_to_end " + json.dumps(e2e))
    log(f"peak_bytes_in_use {dev['memory_peak_bytes']}")
    out = {"window": win, "e2e": e2e, "device": dev, "attempted": len(due),
           "max_len": max_len_of(mix)}
    if trace:
        import shutil
        from bench import trace_reduce
        log(f"pallas_calls_in_step {eng.pallas_calls_in_step()}")
        red = trace_reduce.reduce_dir(tdir)
        if keep_trace:
            shutil.copytree(tdir, keep_trace, dirs_exist_ok=True)
        shutil.rmtree(tdir, ignore_errors=True)
        log("idle_by_span " + json.dumps(red["idle_by_span"]))
        out["reader_ctx"] = {
            "spec": spec, "mix": mix, "window": win, "trace": red,
            "snap": snap, "e2e": e2e, "weight_bits": weight_bits(eng),
            "device_kind": dev["kind"], "max_batch": int(spec["max_batch"])}
        dev["busy_s"] = red["busy_s"]
        dev["window_s"] = red["window_s"]
        out["breakdown"] = red["breakdown"]
    del eng
    gc.collect()
    return out


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float, cfg=None, devs=None, patch=None,
        keep_trace: Optional[str] = None) -> dict:
    """One run of ``cell``: set-up, the window, the comparison. Returns
    the result object."""
    from bench import check as CHK
    got = serve(cell, seed, seconds, trace, t_start, cfg, devs, patch,
                keep_trace)
    metrics: dict = {}
    if trace:
        ctx = got["reader_ctx"]
        for m in cell.per_layer:
            val = load_reader(m["name"])(ctx)
            if val is None:
                raise BenchError(f"per-layer metric {m['name']} found "
                                 f"nothing to read in this cell")
            metrics[m["name"]] = {"value": val, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            if m["name"] in got["e2e"]:
                metrics[m["name"]] = {"value": got["e2e"][m["name"]],
                                      "unit": m["unit"]}
    log("metrics " + json.dumps(metrics))
    verdict = CHK.compare(cell, got["window"], seed, got["max_len"])
    out = {"correct": verdict["correct"],
           "attempted": got["attempted"],
           "failed": 0,
           "metrics": metrics,
           "device": got["device"]}
    if "breakdown" in got:
        out["breakdown"] = got["breakdown"]
    out["compared"] = verdict["compared"]
    return out


def weight_bits(eng) -> dict:
    """{rung bits: {weight shape (K, N): magnitude bits + 1 sign bit}} of
    the codes each rung serves, the width the roofline counts."""
    import jax.numpy as jnp
    from bench import reference
    pts = {}
    leaves = []

    def walk(node):
        if isinstance(node, dict):
            if "w_q" in node:
                leaves.append(node["w_q"])
                return
            for v in node.values():
                walk(v)
        elif isinstance(node, (list, tuple)):
            for v in node:
                walk(v)

    walk(eng.weight_store)
    amax = {}
    for w in leaves:
        shape = tuple(w.shape[-2:])
        m = int(jnp.max(jnp.abs(w.astype(jnp.int32))))
        amax[shape] = max(amax.get(shape, 0), m)
    r_max = max(op.r for op in eng.ladder)
    for op in eng.ladder:
        sh = reference.rung_shift(r_max, op.r)
        pts[op.bits] = {s: max(int(m >> sh).bit_length(), 1) + 1
                        for s, m in amax.items()}
    return pts
