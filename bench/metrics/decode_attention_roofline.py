"""Share of its roofline that the bit-plane KV-cache decode attention
reaches: over the traced window, the least time the chip needs for every
attention call (bench/flops.attention_min_s over the positions cached at
that step, at the rung's cache bits) over the device time of the attention
kernel's events."""
from bench import flops, peaks, trace_reduce

KERNEL = r"_decode_attention_kernel|decode_attention"


def read(ctx):
    ev = trace_reduce.kernel_events(ctx["trace"], KERNEL)
    layers = flops.attention_layers(ctx["spec"])
    if not layers or not ev["n"] or ev["s"] <= 0:
        return None
    spec, pk = ctx["spec"], peaks.peaks(ctx["device_kind"])
    snap = ctx["snap"]
    pts = spec["operating_points"]
    work = 0.0
    for t, rung, pos in ctx["window"].steps:
        if snap["t0"] <= t <= snap["t1"]:
            cb = int(pts[str(rung)]["cache_bits"])
            work += layers * flops.attention_min_s(ctx["max_batch"], pos + 1,
                                                   spec, cb, pk)
    return 100.0 * work / ev["s"]
