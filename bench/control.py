"""The control of the comparison that decides ``correct``, on the chip.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 40

For each seed, in one process: the cell's set-up and window as a benchmark
run makes them, then on the same sampled lanes the program's reading (the
widest gap of a served token below the f32 reference's best) and the
control's (the same, for the token the reference computed in bfloat16
puts first). One JSON line per seed, with the verdict of each side at the
cell's committed limits (bench/checks/<cell>.json): the program must come
out correct and the control not. The limit lies between the largest
program reading and the smallest control reading (PERF.md). The
benchmark's own runs do not run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control-seeds", type=int, default=1 << 30,
                    help="read the control on the first N seeds only")
    args = ap.parse_args(argv)
    import jax
    from bench import check, harness
    from repro.launch.compile_cache import enable_compile_cache
    if jax.devices()[0].platform != "tpu":
        harness.log("no TPU")
        return 2
    enable_compile_cache()
    cell = harness.load_cell(args.workload)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        got = harness.serve(cell, seed, args.seconds, False, t)
        r = check.readings(cell.spec, seed, got["window"], got["max_len"],
                           control=i < args.control_seeds)
        for side in ("program", "control"):
            if side in r:
                r[side + "_correct"] = check.verdict(
                    cell.check, r[side], r["tokens"],
                    len(r["rungs"]))["correct"]
        r.update(seed=seed, workload=args.workload, e2e=got["e2e"],
                 seconds=time.perf_counter() - t)
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
