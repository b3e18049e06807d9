"""Share of the decode step's device time spent in the RWKV-6 wkv decode
kernel: the kernel's events over the traced window, over the events of the
step's executable (the module with the most device time, as
decode_step_ms reads it)."""
from bench import trace_reduce

KERNEL = r"^%rwkv6_wkv_decode\b"


def read(ctx):
    ev = trace_reduce.kernel_events(ctx["trace"], KERNEL)
    mods = ctx["trace"]["modules"]
    if not ev["n"] or not mods:
        return None
    step_s = max(v["s"] for v in mods.values())
    if step_s <= 0:
        return None
    return 100.0 * ev["s"] / step_s
