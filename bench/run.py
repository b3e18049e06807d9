"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (weights from the seed, the weight store, warm-up) is timed as
``setup_s`` from process start; then the window runs for ``--seconds``;
then the served tokens are compared with the plain reference. The last
line of stdout is the result object. Without a TPU, with fewer chips than
the cell asks for, or with the program missing, the run exits nonzero and
prints no result.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="directory to copy the raw profiler trace into")
    args = ap.parse_args(argv)
    from bench import harness
    try:
        cell = harness.load_cell(args.workload)
        import jax
        from repro.launch.compile_cache import enable_compile_cache
        devs = jax.devices()
        if devs[0].platform != "tpu":
            raise harness.BenchError(
                f"no TPU: JAX found {devs[0].platform} devices")
        if len(devs) < cell.chips:
            raise harness.BenchError(
                f"the cell needs {cell.chips} chips, JAX found {len(devs)}")
        harness.log(f"compile cache {enable_compile_cache()}")
        out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                          T_START, devs=devs[:cell.chips],
                          keep_trace=args.keep_trace)
    except harness.BenchError as e:
        harness.log(f"FAILED: {e}")
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
