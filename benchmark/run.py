#!/usr/bin/env python3
"""One run of one cell:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result: ``correct``, ``attempted``,
``failed``, ``metrics`` (end-to-end with ``--trace 0``, per-layer with
``--trace 1``), ``device`` and, traced, ``breakdown``; then, ignored by the
driver, ``workload`` and ``compared``: each number that decided ``correct``
beside its limit, which are also the last lines of standard error.

No accelerator, fewer chips than the cell asks for, a compile inside the
measured window, or a checkout without the program: another exit code than
0 and no result line.  ``BENCH_RUN`` in the environment is not read.
"""

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark import harness

    try:
        cell = harness.Cell(os.path.join(ROOT, "BENCHMARK.json"),
                            args.workload)
        driver = cell.config["program"]["entry"]
        if driver == "serve":
            from benchmark import serve_driver as drv
        elif driver == "train":
            from benchmark import train_driver as drv
        else:
            raise harness.BenchError(f"no driver for entry {driver!r}", 2)
        drv.run(cell, args.seed, args.seconds, bool(args.trace), T_PROCESS)
    except harness.BenchError as e:
        print(f"benchmark: {e}", file=sys.stderr, flush=True)
        return e.code
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # Daemon threads of the program (HTTP front, writers) must not keep a
    # finished run alive past its result line.
    os._exit(code)
