"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is an entry of ``BENCHMARK.json`` at the repository's root; its
configuration, traffic and load are files under ``bench/`` found by name
(``bench/harness.py``).  The run refuses any platform but a TPU, sets up
and warms up (``setup_s``), drives the serving scheduler open loop for
``--seconds``, checks what it served against a float32 reference, and
prints one JSON object as its last line of output: with ``--trace 0`` the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics read
from a profiler trace of the window.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(HERE), "src")]
# the TPU runtime logs to a fixed /tmp directory unless told otherwise; a
# run writes only inside its checkout and its own TMPDIR
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    import harness
    return harness.main(a.workload, a.seed, a.seconds, bool(a.trace),
                        T_START)


if __name__ == "__main__":
    sys.exit(main())
