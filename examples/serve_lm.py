"""Power-accuracy traversal serving example: one server process, a ladder of
PANN operating points, per-request power budgets (repro.serve_engine).

Each request declares the power it can afford (as an unsigned-MAC bit
budget); the engine picks the matching rung from its cached int8 variants
and reports the estimated bit-flip price per generated token in the
response metadata.

    PYTHONPATH=src python examples/serve_lm.py --arch llama3-8b
"""
import argparse
import sys

sys.path.insert(0, "src")

from repro.launch import serve  # noqa: E402
from repro.serve_engine import build_ladder, select_rung  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--ladder", default="2,4,6")
    ap.add_argument("--budgets", default="4,2,6")
    ap.add_argument("--gen", type=int, default=12)
    args = ap.parse_args(argv)

    n_requests = 6
    summary = serve.main([
        "--arch", args.arch, "--reduced",
        "--power_ladder", args.ladder, "--budgets", args.budgets,
        "--batch", "2", "--prompt_len", "16", "--gen", str(args.gen),
        "--requests", str(n_requests)])

    # assert the output shape so this example can't rot silently
    assert summary["mode"] == "ladder"
    reqs = summary["requests"]
    assert len(reqs) == n_requests
    for r in reqs:
        assert len(r["token_ids"]) == args.gen
        for key in ("rung_bits", "b_x_tilde", "r", "tokens",
                    "est_bitflips_per_token", "est_bitflips_total"):
            assert key in r, key
        assert r["tokens"] == args.gen
    assert summary["engine"]["compilations_after_warmup"] == 1
    served = sorted({r["rung_bits"] for r in reqs})
    # expected rungs follow from the flags: map each budget through the
    # ladder's selection policy (budget-path selection depends only on bits)
    ladder = build_ladder([int(b) for b in args.ladder.split(",")])
    expected = sorted({select_rung(ladder, power_budget_bits=int(b)).bits
                       for b in args.budgets.split(",")})
    assert served == expected, (served, expected)

    print(f"served {summary['arch']}: {n_requests} requests across "
          f"{len(served)} power rungs {served} (bits), one compiled step, "
          f"{summary['tok_per_s']} tok/s (CPU)")
    for r in reqs:
        print(f"  request {r['uid']}: rung {r['rung_bits']}b -> "
              f"{r['est_gbitflips_per_token']*1e3:.3f} Mbit-flips/token")
    return summary


if __name__ == "__main__":
    main()
