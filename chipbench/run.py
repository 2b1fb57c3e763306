"""The on-chip benchmark of the Binary Bleed k-search.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the checkout root. It finds the cell in ``BENCHMARK.json``, makes
V on the device from ``--seed``, runs one warm-up search (set-up, which
loads or compiles every program the window runs), then searches back to
back for ``--seconds`` and checks a search drawn from the seed against the
plain reference. The last line of standard output is the result as one
JSON object; earlier lines on standard error say how set-up, the window and
the check went, and its last lines give each compared number and its limit.
With ``--trace 1`` the window runs under the profiler and the result holds
the per-layer metrics instead of the end-to-end ones.

It exits non-zero, printing no result, when JAX holds fewer TPU chips than
the cell asks for, or when the program's ``src/`` is not in the checkout.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"chipbench: the program's src/repro is not in {ROOT}", file=sys.stderr)
        return 2
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from chipbench import harness

    cell = harness.load_cell(ROOT, args.workload)
    device = harness.require_chips(cell.chips)
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace), T0, device)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
