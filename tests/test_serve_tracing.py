"""The engine's own instrumentation: ``serve.*`` host spans in a profiler
trace, the counters ``describe()`` reports (steps, and the packed plane
bytes a step reads in place and through copies), and the device names of
the decode step (module ``jit_decode_step``, scopes ``ssm_state`` and
``act_range``). Reduced zamba2 (and RWKV-6) on CPU."""
import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import configs
from repro.models import model as MD
from repro.models import transformer as T
from repro.serve_engine import (Request, ServeEngine, build_ladder,
                                select_rung)
from repro.serve_engine.scheduler import Scheduler

LADDER = (2, 4, 6)
MAX_BATCH = 2
# (max_new_tokens, budget bits): three rungs, the 2-bit one in two waves
MIX = [(3, 2), (5, 2), (2, 4), (4, 6), (3, 6), (6, 4), (4, 2)]
CHILDREN = {"serve.init_state": {"serve.prefill"},
            "serve.dispatch": {"serve.prefill", "serve.step"},
            "serve.sample": {"serve.prefill", "serve.step"}}


@pytest.fixture(scope="module")
def setup():
    cfg = configs.reduced(configs.get_config("zamba2-1.2b"))
    return cfg, MD.init_params(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def engine(setup):
    cfg, params = setup
    eng = ServeEngine(cfg, params, ladder_bits=LADDER, max_batch=MAX_BATCH,
                      max_len=16)
    eng.warmup()
    return eng


def _requests(cfg):
    rng = np.random.default_rng(0)
    return [Request(uid=i, max_new_tokens=n, power_budget_bits=b,
                    prompt=rng.integers(0, cfg.vocab_size, 3).astype(np.int32))
            for i, (n, b) in enumerate(MIX)]


def _waves(eng, reqs):
    """The waves the scheduler forms, counted here: per rung, in
    submission order, up to max_batch requests of equal prompt length."""
    by_rung: dict = {}
    for r in reqs:
        by_rung.setdefault(select_rung(eng.ladder, r.power_budget_bits).bits,
                           []).append(r)
    return [rs[i:i + MAX_BATCH] for rs in by_rung.values()
            for i in range(0, len(rs), MAX_BATCH)]


@pytest.fixture(scope="module")
def traced(engine, setup, tmp_path_factory):
    """generate() under the profiler: (host spans, counter deltas)."""
    cfg, _ = setup
    before = engine.describe()
    d = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(d):
        engine.generate(_requests(cfg))
    after = engine.describe()
    path = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0]
    spans = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    spans.append((ev.name, ev.start_ns, ev.end_ns,
                                  {k: v for k, v in ev.stats}))
    delta = {k: after[k] - before[k]
             for k in ("decode_steps", "prefill_steps", "live_rows")}
    return sorted(spans, key=lambda s: (s[1], -s[2])), delta


def _parent(span, spans):
    """The innermost other serve span that holds ``span``."""
    holders = [s for s in spans if s is not span and s[1] <= span[1]
               and span[2] <= s[2]]
    return min(holders, key=lambda s: s[2] - s[1]) if holders else None


def test_every_span_appears_and_nests(traced, engine):
    spans, delta = traced
    names = {s[0] for s in spans}
    assert names == {"serve.next_wave", "serve.prefill", "serve.init_state",
                     "serve.dispatch", "serve.sample", "serve.step"}
    for s in spans:
        parent = _parent(s, spans)
        if s[0] in CHILDREN:
            assert parent is not None and parent[0] in CHILDREN[s[0]], s
            # a child carries its parent's wave and rung
            assert s[3] == parent[3], (s, parent)
        else:
            assert parent is None, (s, parent)
    n_dispatch = sum(s[0] == "serve.dispatch" for s in spans)
    assert n_dispatch == delta["prefill_steps"] + delta["decode_steps"]


def test_spans_carry_the_wave_number_and_rung(traced, engine, setup):
    spans, _ = traced
    waves = _waves(engine, _requests(setup[0]))
    prefills = [s[3] for s in spans if s[0] == "serve.prefill"]
    assert len(prefills) == len(waves)
    # every wave its own number, each number's spans at one rung
    assert len({p["wave"] for p in prefills}) == len(waves)
    rung_of = {p["wave"]: p["rung"] for p in prefills}
    assert set(rung_of.values()) == set(LADDER)
    for name, _, _, args in spans:
        if name != "serve.next_wave" or args:
            assert rung_of[args["wave"]] == args["rung"], (name, args)
    steps = {}
    for name, _, _, args in spans:
        if name == "serve.step":
            steps[args["wave"]] = steps.get(args["wave"], 0) + 1
    assert sorted(steps.values()) == sorted(
        max(r.max_new_tokens for r in w) - 1 for w in waves
        if max(r.max_new_tokens for r in w) > 1)


def test_counters_equal_an_independent_count(traced, engine, setup):
    _, delta = traced
    reqs = _requests(setup[0])
    waves = _waves(engine, reqs)
    assert delta["live_rows"] == sum(r.max_new_tokens - 1 for r in reqs)
    assert delta["decode_steps"] == sum(
        max(r.max_new_tokens for r in w) - 1 for w in waves)
    assert delta["prefill_steps"] == sum(r.prompt_len for r in
                                         (w[0] for w in waves))


def test_decode_stream_counts_through_the_same_calls(engine):
    before = engine.describe()
    out = engine.decode_stream(np.arange(3, dtype=np.int32),
                               [(2, 3), (6, 0), (4, 2)])
    after = engine.describe()
    assert [len(s["tokens"]) for s in out["segments"]] == [3, 0, 2]
    # a segment of n tokens: one prefill, n - 1 decode steps of one live row
    assert after["decode_steps"] - before["decode_steps"] == 3
    assert after["live_rows"] - before["live_rows"] == 3
    assert after["prefill_steps"] - before["prefill_steps"] == 3 + 6


def test_scheduler_numbers_its_waves():
    ladder = build_ladder(LADDER, d=64.0)
    sched = Scheduler(ladder, max_batch=1)
    for i in range(3):
        sched.submit(Request(uid=i, prompt=np.zeros(2, np.int32)),
                     rung=ladder[i % len(ladder)])
    assert [sched.next_wave().seq for _ in range(3)] == [0, 1, 2]
    assert sched.next_wave() is None


def test_decode_step_names_its_module_and_scopes(setup):
    """With the packed backend (interpret mode on CPU), the lowered step
    is module jit_decode_step and carries both scope names."""
    cfg, params = setup
    eng = ServeEngine(cfg, params, ladder_bits=LADDER, max_batch=MAX_BATCH,
                      max_len=16, backend="packed:force")
    tok = jnp.zeros((MAX_BATCH, 1), jnp.int32)
    text = eng._step.lower(eng.variants[2], eng._init_state(2),
                           tok).as_text(debug_info=True)
    assert "module @jit_decode_step" in text
    assert "ssm_state" in text
    assert "act_range" in text


def _plane_bytes(variant, cfg) -> dict:
    """The packed planes one decode step reads, from the store's leaves:
    each module's pos and neg planes once a step (the zamba2 shared block
    once a use), split by whether a layer's planes fill whole blocks (K a
    multiple of the kernel's 256-row groups, N of its 128 lanes) or have
    to be padded."""
    pattern, n_groups, n_tail = T.group_layout(cfg)
    kinds = [s.kind for s in pattern] * n_groups + \
        [pattern[i % len(pattern)].kind for i in range(n_tail)]
    out = {"in_place": 0, "copied": 0}
    for path, a in jax.tree_util.tree_leaves_with_path(variant):
        if getattr(path[-1], "key", None) not in ("w_planes_pos",
                                                  "w_planes_neg"):
            continue
        _, k8, n = a.shape[-3:]
        uses = (kinds.count("mamba_attn")
                if path[0].key == "shared_attn" else 1)
        padded = (k8 * 8) % 256 or n % 128
        out["copied" if padded else "in_place"] += a.size * uses
    return out


@pytest.mark.parametrize("arch", ["rwkv6-1.6b", "zamba2-1.2b"])
def test_engine_counts_the_plane_bytes_of_a_step(arch):
    """At width 256 some modules fill the packed kernel's blocks and some
    (the LoRAs, the Mamba2 in-projection) do not: ``describe()`` counts the
    first read in place from the stacked store, the padded ones copied,
    and the two add up to the planes of one step."""
    cfg = dataclasses.replace(configs.reduced(configs.get_config(arch)),
                              d_model=256, d_ff=512)
    eng = ServeEngine(cfg, MD.init_params(jax.random.PRNGKey(0), cfg),
                      ladder_bits=(4,), max_batch=MAX_BATCH, max_len=8,
                      backend="packed:force")
    assert eng.describe()["plane_bytes_in_place"] is None
    eng._step.trace(eng.variants[4], eng._init_state(4),
                    jnp.zeros((MAX_BATCH, 1), jnp.int32))
    got = eng.describe()
    want = _plane_bytes(eng.variants[4], cfg)
    assert want["in_place"] and want["copied"]
    assert (got["plane_bytes_in_place"], got["plane_bytes_copied"]) == \
        (want["in_place"], want["copied"])
