"""Share of its roofline that the RWKV-6 wkv decode kernel reaches: over
the traced window, the least time the chip needs for every wkv call (the
family's wkv_bytes: the f32 state read and written, r, k, v and w in, o
out, and u, over the HBM bandwidth; its f32 operations, about 1.3 a byte,
lie far under any of the chip's peaks) over the device time of the
kernel's events. One call per layer a decode step, over ``max_batch``
rows; the steps are the engine's own count over the window."""
from bench import families, peaks, trace_reduce

# the kernel's own instruction name (kernels/rwkv_wkv.NAME), anchored so
# that ops which only read its result do not match
KERNEL = r"^%rwkv6_wkv_decode\b"


def read(ctx):
    ev = trace_reduce.kernel_events(ctx["trace"], KERNEL)
    fam = families.load(ctx["spec"])
    if not ev["n"] or ev["s"] <= 0 or not hasattr(fam, "wkv_bytes"):
        return None
    spec, pk = ctx["spec"], peaks.peaks(ctx["device_kind"])
    snap = ctx["snap"]
    steps = sum(n1 - snap["steps0"].get(rung, 0)
                for rung, n1 in snap["steps1"].items())
    nbytes = steps * spec["num_layers"] * fam.wkv_bytes(spec,
                                                        ctx["max_batch"])
    return 100.0 * nbytes / pk["hbm_bytes_per_s"] / ev["s"]
