"""The whole step's share of the chip's int8 peak: the prompt and generated
tokens of real requests in the window, times 2 x MACs per token
(bench/flops.py), over the window's seconds times the int8 peak."""
from bench import flops, peaks


def read(ctx):
    win = ctx["window"]
    gen = sum(1 for r in win.reqs for t in r.token_times
              if win.t0 <= t <= win.t1)
    tokens = win.prompt_tokens + gen
    if not tokens:
        return None
    spec = ctx["spec"]
    ctx_len = max((ln.prompt.shape[1] + len(ln.tokens)) for ln in win.lanes)
    macs = flops.macs_per_token(spec, ctx_len // 2)
    pk = peaks.peaks(ctx["device_kind"])
    return 100.0 * tokens * 2.0 * macs / ((win.t1 - win.t0)
                                          * pk["int8_ops_per_s"])
