#!/usr/bin/env python3
"""Readings that the limits of a cell's comparison are set from, on the chip.

    python3 benchmarks/chip/calibrate.py --workload granite_3_8b.cut1.sim_w2.allreduce \
        --seeds 11,12,13,14 --controls 11,12,13 --look 11

For every seed of ``--seeds`` it runs the program's first three steps through
the same set-up as a benchmark run and prints their gaps to the float32
reference at ``highest`` precision (the lower readings), with the leaves
that gap most. For every seed of ``--controls`` it also prints the gaps of
the reference put in the program's place computed in bfloat16 (the control),
with each planted fault (the loss over half of each worker's rows; the
gradient mean left out). For every seed
of ``--look``, the look behind a number: the reference in float32 at the
TPU's default matmul precision, with the leaves that gap most. A
state left unchanged reads 1 on ``update_gap`` by construction and needs no
run. One JSON line per seed, each seed in a process of its own (the parent
never touches JAX, so each child holds the chip alone). The benchmark's own
runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))


def one_seed(workload: str, seed: int, controls: bool, look: bool) -> dict:
    import jax.numpy as jnp

    import compare
    import harness

    cell = harness.load_cell(workload)
    devices, _ = harness.require_chips(cell)
    harness.enable_cache()
    run = harness.prepare(cell, seed, devices)
    run.trainer = run.state = run.pool = None
    gc.collect()
    ref = harness.reference(cell, run, devices)
    out = {"seed": seed, "program": compare.gaps(run.prog, ref),
           "program_worst": compare.worst_leaves(run.prog, ref, ref["names"])}
    if controls:
        out["control_bf16"] = compare.gaps(
            harness.reference(cell, run, devices, dtype=jnp.bfloat16), ref)
        for fault in ("half", "nomean"):
            out[f"fault_{fault}"] = compare.gaps(
                harness.reference(cell, run, devices, fault=fault), ref)
    if look:
        default = harness.reference(cell, run, devices, precision="default")
        out["reference_default_precision"] = compare.gaps(default, ref)
        out["reference_default_worst"] = compare.worst_leaves(default, ref, ref["names"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--controls", default="")
    ap.add_argument("--look", default="",
                    help="seeds that also run the reference at the default precision")
    ap.add_argument("--one", type=int, help="run this seed in this process")
    args = ap.parse_args(argv)
    controls = {int(s) for s in args.controls.split(",") if s}
    look = {int(s) for s in args.look.split(",") if s}
    if args.one is not None:
        print(json.dumps(one_seed(args.workload, args.one, args.one in controls,
                                  args.one in look)), flush=True)
        return 0
    seeds = sorted({int(s) for s in args.seeds.split(",") if s} | controls | look)
    for seed in seeds:
        p = subprocess.run([sys.executable, __file__, "--workload", args.workload,
                            "--controls", args.controls, "--look", args.look,
                            "--one", str(seed)],
                           capture_output=True, text=True)
        lines = [l for l in p.stdout.splitlines() if l.startswith('{"seed"')]
        if p.returncode or not lines:
            print(json.dumps({"seed": seed, "rc": p.returncode,
                              "stderr": p.stderr[-2000:]}), flush=True)
        else:
            print(lines[-1], flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
