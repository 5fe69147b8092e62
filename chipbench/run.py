"""Run one benchmark cell once, on the chip this process finds.

  python3 chipbench/run.py --workload mtb16_vt_cls --seed 7 --seconds 40 --trace 0

The last line of standard output is the result as one JSON object; the
numbers compared with the reference, each beside its limit, are the last
lines of standard error. Without a TPU it exits non-zero and prints no
result.
"""
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    raise SystemExit(harness.main(t_start=T_START))
