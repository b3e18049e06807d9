"""Share of the window's model steps spent teacher-forcing prompts
(prefill runs through the decode step, one prompt token per step)."""


def read(ctx):
    win = ctx["window"]
    total = win.prefill_steps + win.decode_steps
    if not total:
        return None
    return 100.0 * win.prefill_steps / total
