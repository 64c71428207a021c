"""The benchmark's one command.

    python3 perf/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell named in ``BENCHMARK.json``, warms up, measures for
``--seconds``, checks the result and prints one JSON object as the last line
of its standard output. Any failure exits nonzero and prints no result.
"""
import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    sys.path.insert(0, str(CHECKOUT))
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--allow-cpu", action="store_true",
                        help="tests only: run on the CPU backend; prints "
                             "counts and no device metric")
    parser.add_argument("--root", type=Path, default=CHECKOUT,
                        help="directory of the BENCHMARK.json to read "
                             "(default: this checkout)")
    parser.add_argument("--record", default=None,
                        help="also write the run's record (per-chunk series "
                             "included) to this JSON file")
    parser.add_argument("--dump-trace", default=None,
                        help="with --trace 1: keep the device planes of the "
                             "trace as .json.gz (how perf/testdata was made)")
    parser.add_argument("--dump-hlo", default=None,
                        help="also write the chunk program's optimized HLO "
                             "text as .txt.gz (the stage table's source; "
                             "how perf/testdata was made)")
    args = parser.parse_args(argv)

    from perf.harness import run_cell
    from perf.harness.manifest import Manifest

    if args.seconds is None:
        args.seconds = float(Manifest(args.root).run_seconds)
    return run_cell.run(args, T_PROCESS_START)


if __name__ == "__main__":
    sys.exit(main())
