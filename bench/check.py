"""How ``correct`` is decided: the served tokens against the plain reference.

Once the window has closed and the program's state is freed, a sample of
the lanes that finished requests is drawn from the seed: one lane of each
rung that finished any, and always the lane of the longest finished
request. The reference (bench/reference.py) re-decodes each sampled lane
from its prompts and the tokens the program served, the whole batch in
lockstep as it was served, and at every served token of a finished request
reads how far that token's logit lies below the reference's best. The
widest such gap is compared with the cell's limit (bench/checks/<cell>
.json), where PERF.md gives the readings the limit was set from.
"""
from __future__ import annotations

import gc
import sys

import numpy as np


def pick_lanes(win, seed: int) -> list:
    """The sampled lanes: one per rung, drawn from the seed, plus the lane
    of the longest finished request."""
    rng = np.random.default_rng(int(seed) + 7)
    done = [ln for ln in win.lanes if any(r.finished for r in ln.reqs)]
    if not done:
        return []
    longest = max((r for ln in done for r in ln.reqs if r.finished),
                  key=lambda r: (r.item.max_new_tokens, -r.lane))
    picked = {done[[ln.uid for ln in done].index(longest.lane)].uid}
    for bits in sorted({ln.rung for ln in done}):
        cands = [ln for ln in done if ln.rung == bits]
        if not any(ln.uid in picked for ln in cands):
            picked.add(cands[int(rng.integers(len(cands)))].uid)
    return [ln for ln in done if ln.uid in picked]


def lane_tokens(lrec) -> np.ndarray:
    """(max_batch, L + G) prompt rows followed by every served token."""
    gen = np.stack(lrec.tokens, axis=1).astype(np.int32)
    return np.concatenate([lrec.prompt, gen], axis=1)


def served_gaps(gaps: np.ndarray, lrec) -> list:
    """Per finished request of the lane, the gaps of its served tokens."""
    plen = lrec.prompt.shape[1]
    return [gaps[r.row, plen - 1:plen - 1 + r.item.max_new_tokens]
            for r in lrec.reqs if r.finished]


def reference_gaps(spec: dict, seed: int, lanes: list, max_len: int,
                   dtype=None, scored_by=None) -> list:
    """Gaps of each sampled lane's served tokens under the reference at
    ``dtype``. ``scored_by`` (lane uid -> (B, T-1) tokens) scores other
    tokens than the served ones at each position."""
    import jax.numpy as jnp
    from bench import reference, weights
    params = weights.make_params(spec, seed)
    ref = reference.Reference(spec, params, dtype or jnp.float32)
    del params
    gc.collect()
    out = []
    for bits in sorted({ln.rung for ln in lanes}):
        rung = ref.rung(bits)
        for lrec in (ln for ln in lanes if ln.rung == bits):
            scored = None if scored_by is None else scored_by[lrec.uid]
            gaps, tops = ref.run(rung, lane_tokens(lrec), max_len,
                                 scored=scored)
            out.append((lrec, np.asarray(gaps), np.asarray(tops)))
        del rung
    del ref
    gc.collect()
    return out


def verdict(check: dict, worst: float, tokens: int, rungs: int) -> dict:
    """``correct`` and the numbers compared, each beside its limit."""
    compared = {
        "max_logit_gap": {"value": worst,
                          "limit": float(check["max_logit_gap"]["limit"])},
        "tokens_compared": {"value": tokens,
                            "limit": int(check.get("min_tokens", 1))},
        "rungs_compared": {"value": rungs,
                           "limit": int(check.get("min_rungs", 1))},
    }
    ok = (worst <= compared["max_logit_gap"]["limit"]
          and tokens >= compared["tokens_compared"]["limit"]
          and rungs >= compared["rungs_compared"]["limit"])
    return {"correct": bool(ok), "compared": compared}


def compare(cell, win, seed: int, max_len: int) -> dict:
    import time
    t = time.perf_counter()
    lanes = pick_lanes(win, seed)
    worst, n = 0.0, 0
    for lrec, gaps, _ in reference_gaps(cell.spec, seed, lanes, max_len):
        for g in served_gaps(gaps, lrec):
            if g.size:
                worst = max(worst, float(g.max()))
                n += int(g.size)
    print(f"[bench] reference_s {time.perf_counter() - t} lanes "
          f"{[(ln.uid, ln.rung, len(ln.tokens)) for ln in lanes]}",
          file=sys.stderr, flush=True)
    out = verdict(cell.check, worst, n, len({ln.rung for ln in lanes}))
    for name, v in out["compared"].items():
        print(f"{name} {v['value']} limit {v['limit']}", file=sys.stderr,
              flush=True)
    return out


def readings(spec: dict, seed: int, win, max_len: int,
             control: bool = True) -> dict:
    """Both readings on one run's sampled lanes: the program's widest gap
    (its served tokens under the f32 reference) and the control's (at each
    position, the token the bfloat16 reference puts first, scored by the
    f32 reference), each with the quantiles of all its gaps."""
    import jax.numpy as jnp
    lanes = pick_lanes(win, seed)

    def gaps_of(res):
        return np.concatenate([g for lrec, gaps, _ in res
                               for g in served_gaps(gaps, lrec)] or
                              [np.zeros(0)])

    def stats(g):
        if not g.size:
            return {}
        return {"max": float(g.max()), "p99": float(np.quantile(g, 0.99)),
                "mean": float(g.mean()), "nonzero": float((g > 0).mean())}

    prog = gaps_of(reference_gaps(spec, seed, lanes, max_len))
    out = {"program": float(prog.max()) if prog.size else 0.0,
           "tokens": int(prog.size), "program_stats": stats(prog),
           "rungs": sorted({ln.rung for ln in lanes})}
    if not control:
        return out
    ctrl = reference_gaps(spec, seed, lanes, max_len, dtype=jnp.bfloat16)
    tops = {lrec.uid: t for lrec, _, t in ctrl}
    cg = gaps_of(reference_gaps(spec, seed, lanes, max_len, scored_by=tops))
    out["control"] = float(cg.max()) if cg.size else 0.0
    out["control_stats"] = stats(cg)
    return out
