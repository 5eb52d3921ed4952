#!/usr/bin/env python3
"""Readings of the control and of the planted fault, at a cell's size.

    python3 benchmark/control.py --workload <cell>[,<cell>] --seeds 1,2,3

Not part of a benchmark run, and it needs no program.  It follows the
plain reference over the cell's first steps, on every seed: as it is;
in fp8 (`check.Fp8`), the nearest precision below the bfloat16 the
configurations state (the control); with rows of every batch left out
and the mean taken over the rest (`rows_left_out`: all but one chip's
share, and half the batch on one chip); and with `--bf16 1` in
bfloat16, a second witness of what the stated precision alone does to
each number.  Each is compared with the first as a run compares the
program with it, and every number is printed, one JSON line a seed.
The limits in `benchmark/limits/` were set from these (PERF.md).
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--bf16", type=int, default=0,
                    help="1: also the reference in bfloat16, a witness")
    args = ap.parse_args(argv)
    for workload in args.workload.split(","):
        read_cell(workload, args)


def read_cell(workload, args):
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from benchmark import check, harness, traffic, weights
    cell, cfg, mix, _manifest, limits = harness.load_cell(ROOT, workload)
    reference, _counts = harness.family_of(cfg)
    chips = cell["chips"]
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        if os.environ.get("JAX_PLATFORMS") != "cpu":
            sys.exit("control.py: needs %d TPU chip(s)" % chips)
        cfg, mix = harness.tiny(cfg, mix)
    harness.set_compile_cache(ROOT)
    arch = reference.arch_of(cfg)
    opt = {k: cfg["optimizer"][k] for k in ("learning_rate", "momentum", "wd")}
    mesh = Mesh(np.array(devices[:chips]), ("dp",))
    rows = NamedSharding(mesh, PartitionSpec("dp"))
    whole = NamedSharding(mesh, PartitionSpec())
    shapes = reference.param_shapes(arch)
    batch = cfg["per_chip_batch"] * chips
    seeds = [int(s) for s in args.seeds.split(",")]

    def follow(seed, arith, keep):
        t0 = time.perf_counter()
        batches = traffic.own_batches(mix, cfg, seed, chips, args.steps,
                                      rows)
        p, a = weights.make(seed, *shapes, whole)
        out = reference.follow(
            p, a, [(x[:keep], y[:keep]) for x, y in batches], arch, opt,
            arith=arith, sharding=rows)
        out["seconds"] = time.perf_counter() - t0
        return out

    plan = [("reference", reference.Exact, batch),
            ("fp8_control", check.Fp8, batch),
            ("rows_left_out", reference.Exact, batch // max(chips, 2))]
    if args.bf16:
        plan.append(("bf16_witness", check.Bf16, batch))
    # one program at a time, over every seed: each holds the chip's
    # memory for its temporaries, and four side by side do not fit
    got = {}
    for name, arith, keep in plan:
        got[name] = {seed: follow(seed, arith, keep) for seed in seeds}
        reference.release()
        jax.clear_caches()
    for seed in seeds:
        ref = got["reference"][seed]
        line = {"workload": workload, "seed": seed,
                "reference_s": ref["seconds"], "loss": ref["loss"]}
        cases = {name: got[name][seed] for name, _a, _k in plan[1:]}
        for name, case in cases.items():
            numbers = check.compare(case, ref, reference.products(arch))
            line[name] = {k: v[0] for k, v in numbers.items()}
            line[name]["where"] = {k: v[1] for k, v in numbers.items()
                                   if k.endswith("_gap")}
            line[name]["fails"] = [k for k, v in numbers.items()
                                   if k in limits and not v[0] <= limits[k]]
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
