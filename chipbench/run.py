"""python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of BENCHMARK.json on the machine it is started on and
prints one JSON object as the last line of standard output. Exits
without a result when JAX finds no TPU or fewer chips than the cell
asks for.
"""

import time

T_PROC = time.perf_counter()

import argparse   # noqa: E402
import json       # noqa: E402
import os         # noqa: E402
import sys        # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    here = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(here)
    sys.path.insert(0, root)            # the program under test
    import harness

    cell = harness.load_cell(root, args.workload)
    # the program's own entry points place the cache themselves; the
    # library takes the one the benchmark gives it
    harness.setup_compile_cache(root)
    devices = harness.require_tpu(cell.chips)
    result = harness.run_cell(root, cell, args.seed, args.seconds,
                              bool(args.trace), devices, T_PROC)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
