#!/usr/bin/env python3
"""The benchmark's command: one run of one cell in this process.

    python3 benchmark/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The last line of standard output is the result (one JSON object); what
was compared, beside its limits, ends standard error.  Exit codes: 0 a
result was printed; 2 JAX shows no TPU, or fewer chips than the cell
asks for; 3 a rehearsal (JAX_PLATFORMS=cpu, a tiny shape) ran to its
end and measured no device.  The process holds the chips itself and
starts no child.
"""
import argparse
import json
import logging
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    if not os.path.isdir(os.path.join(ROOT, "mxnet_tpu")):
        sys.exit("benchmark/run.py: no mxnet_tpu beside the benchmark "
                 "(%s): there is no system to measure" % ROOT)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    logging.basicConfig(level=logging.WARNING, stream=sys.stderr)
    from benchmark import harness
    code, result = harness.run(ROOT, args.workload, args.seed, args.seconds,
                               bool(args.trace), T_START)
    if result is not None:
        sys.stderr.flush()
        print(json.dumps(result), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
