"""The wave/lane loop and the comparison, at a reduced size on the CPU,
through the harness's internal functions (not the CLI).

A sound run must come out correct; a run whose served path is broken
underneath must not: a decode step that hands back its state unchanged,
a token altered where it is produced, and half of the batch left out.
The limit here is for the
reduced size (logits of a 64-wide model are smaller than at full width):
there the program reads 0 exactly on CPU, and the bfloat16 control 0.36
and above (see test_control.py)."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.tests.util import reduced_cell

LIMIT = 0.1
SEED = 2**31 + 5
CASES = [("zamba2-1.2b", "closed"), ("rwkv6-1.6b", "closed"),
         ("zamba2-1.2b", "open")]


def cut(config, loop="closed"):
    return reduced_cell(config, loop, limit=LIMIT)


def run(config, loop="closed", seconds=4.0, patch=None, trace=False):
    cell, cfg = cut(config, loop)
    return harness.run(cell, SEED, seconds, trace, time.perf_counter(),
                       cfg=cfg, devs=jax.devices()[:1], patch=patch)


@pytest.mark.parametrize("config,loop", CASES)
def test_sound_run_is_correct(config, loop):
    out = run(config, loop)
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    names = set(out["metrics"])
    assert "setup_s" in names and "decode_tok_per_s" in names
    assert list(out)[-1] == "compared"


def test_window_records_every_token():
    cell, cfg = cut("zamba2-1.2b")
    got = harness.serve(cell, SEED, 3.0, False, time.perf_counter(),
                        cfg=cfg, devs=jax.devices()[:1])
    win = got["window"]
    done = [r for r in win.reqs if r.finished]
    assert done
    for r in win.reqs:
        assert len(r.token_times) <= r.item.max_new_tokens
        assert r.token_times == sorted(r.token_times)
    for ln in win.lanes:
        assert len(ln.reqs) <= cell.spec["max_batch"]
        assert {r.rung for r in ln.reqs} == {ln.rung}
    assert win.useful_rows <= win.decode_steps * cell.spec["max_batch"]
    assert got["e2e"]["decode_tok_per_s"] > 0


def stale_state(eng):
    orig = eng._run_step

    def step(bits, state, tok):
        logits, _ = orig(bits, state, tok)
        return logits, state
    eng._run_step = step


def altered_token(eng):
    """Row 0's token is altered in every step of every lane (two lanes
    alternate, so altering every other call would spare one lane)."""
    orig = eng._greedy

    def greedy(logits):
        tok = np.array(orig(logits))
        tok[0, 0] = (tok[0, 0] + 1) % eng.cfg.vocab_size
        return jnp.asarray(tok)
    eng._greedy = greedy


def half_batch(eng):
    """The second half of the batch left out: its rows get the logits of
    the first half's."""
    orig = eng._run_step

    def step(bits, state, tok):
        logits, state = orig(bits, state, tok)
        lg = np.array(logits)
        h = lg.shape[0] // 2
        lg[lg.shape[0] - h:] = lg[:h]
        return jnp.asarray(lg), state
    eng._run_step = step


@pytest.mark.parametrize("fault", [stale_state, altered_token, half_batch])
@pytest.mark.parametrize("config", ["zamba2-1.2b", "rwkv6-1.6b"])
def test_broken_path_is_not_correct(fault, config):
    out = run(config, patch=fault)
    assert not out["correct"], out["compared"]


def test_trace_run_reports_per_layer_on_cpu_without_device_metrics():
    cell, cfg = cut("zamba2-1.2b")
    got = harness.serve(cell, SEED, 2.0, False, time.perf_counter(),
                        cfg=cfg, devs=jax.devices()[:1])
    ctx = {"window": got["window"], "spec": cell.spec}
    assert 0 < harness.load_reader("rows_per_step")(ctx) <= 3
    share = harness.load_reader("prefill_step_share")(ctx)
    assert 0 < share < 100
