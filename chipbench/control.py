"""Readings that the correctness limits of a cell are set from.

    python3 chipbench/control.py --workload <cell> --seconds <s> --seeds 1,2,... --control-seeds 1,2,3

In one process, for each seed: make V and run the window's searches as a
run with that seed does (for ``--seconds``, without the warm-up), and
compare every search with the plain reference as a run does: the
program's reading. For each control seed, also put the control in the
program's place (the reference with every MU matrix product's operands
rounded to float8_e4m3fn) for the same searches and compare it the same
way. One JSON line per seed on standard output. A limit lies above the
largest program reading and below the smallest control reading.

Like ``run.py`` it needs the cell's TPU chips and exits non-zero without.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONTROL_OPERANDS = "float8_e4m3fn"


def readings(cell, seed: int, seconds: float, control: bool) -> dict:
    from chipbench import harness

    generator = cell.module("data", cell.config["generator"])
    search = cell.module("searches", cell.traffic["search"])
    reference = cell.module("references", cell.config["reference"])
    data_key, search_key, _ = harness.seed_keys(seed)
    v = generator.generate(data_key, **cell.config["params"])
    window = harness.run_window(search, v, search_key, cell.traffic, seconds, False)
    t0 = time.perf_counter()
    program = harness.check_searches(window.searches, v, reference, cell.traffic)
    out = {"seed": seed, "searches": len(window.searches), "wall_s": window.wall_s,
           "reference_s": time.perf_counter() - t0,
           "program": harness.compared_numbers(program, cell.limits["far_gap"]),
           "program_diffs": [c["diff"] for c in program],
           "program_k_optimal": [[c["k_optimal"], c["k_optimal_reference"]] for c in program]}
    if control:
        ctrl = harness.check_searches(window.searches, v, reference, cell.traffic,
                                      operands=CONTROL_OPERANDS)
        out["control"] = harness.compared_numbers(ctrl, cell.limits["far_gap"])
        out["control_diffs"] = [c["diff"] for c in ctrl]
        out["control_k_optimal"] = [c["k_optimal"] for c in ctrl]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--control-seeds", default="", help="comma-separated")
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from chipbench import harness

    cell = harness.load_cell(ROOT, args.workload)
    device = harness.require_chips(cell.chips)
    harness.use_compile_cache()
    control_seeds = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in [int(s) for s in args.seeds.split(",")]:
        out = readings(cell, seed, args.seconds, seed in control_seeds)
        print(json.dumps({**out, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
