"""Share of its roofline that the packed bit-plane matmul reaches: over the
traced window, the least time the chip needs for the work of every packed
projection call (bench/flops.matmul_min_s: the call's rows, its weights at
the width the rung serves, fp32 rows in and out) over the device time of
the packed kernel's events."""
from bench import flops, peaks, trace_reduce

KERNEL = r"pann_matmul_packed_act|_act_kernel"


def read(ctx):
    ev = trace_reduce.kernel_events(ctx["trace"], KERNEL)
    if not ev["n"] or ev["s"] <= 0:
        return None
    spec, pk = ctx["spec"], peaks.peaks(ctx["device_kind"])
    snap = ctx["snap"]
    m = ctx["max_batch"]
    work = 0.0
    for rung, n1 in snap["steps1"].items():
        steps = n1 - snap["steps0"].get(rung, 0)
        bits = ctx["weight_bits"][rung]
        for k, n, calls in flops.projections(spec):
            work += steps * calls * flops.matmul_min_s(m, k, n, bits[(k, n)],
                                                       pk)
    return 100.0 * work / ev["s"]
