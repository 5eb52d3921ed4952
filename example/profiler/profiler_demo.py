"""Profiler demo (reference example/profiler/profiler_matmul.py etc.).

Shows the reference profiling API (set_config / set_state / dump) layered on
the TPU-native implementation: host-side events + native-engine per-op
stamps go into one Chrome-trace JSON (open in chrome://tracing or Perfetto),
and a jax.profiler XPlane trace is captured alongside for TensorBoard.
"""
import argparse
import logging
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import json
import numpy as np
import mxnet_tpu as mx


def main():
    parser = argparse.ArgumentParser(description="profiler demo")
    parser.add_argument("--iter-num", type=int, default=20)
    parser.add_argument("--size", type=int, default=512)
    parser.add_argument("--output", default="profile_matmul.json")
    args = parser.parse_args()
    logging.basicConfig(level=logging.INFO)

    mx.telemetry.enable()   # spans also reach the Chrome trace's ring
    mx.profiler.profiler_set_config(mode="all", filename=args.output)
    mx.profiler.profiler_set_state("run")

    a = mx.nd.array(np.random.rand(args.size, args.size).astype(np.float32))
    b = mx.nd.array(np.random.rand(args.size, args.size).astype(np.float32))
    for i in range(args.iter_num):
        with mx.telemetry.span("matmul_%d" % i):
            c = mx.nd.dot(a, b)
            c.wait_to_read()

    mx.profiler.profiler_set_state("stop")
    mx.profiler.dump_profile()

    with open(args.output) as f:
        events = json.load(f)["traceEvents"]
    logging.info("wrote %s with %d trace events (open in chrome://tracing)",
                 args.output, len(events))
    xplane = os.path.splitext(args.output)[0] + "_xplane"
    if os.path.isdir(xplane):
        logging.info("jax.profiler XPlane trace in %s (TensorBoard)", xplane)


if __name__ == "__main__":
    main()
