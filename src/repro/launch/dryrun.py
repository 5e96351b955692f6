import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes, record memory/cost/roofline artifacts.

MUST be run as its own process (the JAX_PLATFORMS/XLA_FLAGS lines above
precede every other import — jax locks the platform and device count on first
init; the 512 placeholder devices are CPU devices, never an attached chip).
Results accumulate under
``experiments/dryrun/<mesh>/<arch>__<shape>__<program>.json`` so interrupted
sweeps resume where they left off.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama_1_1b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--force]
"""

import argparse     # noqa: E402
import json         # noqa: E402
import time         # noqa: E402
import traceback    # noqa: E402

import jax          # noqa: E402

from repro.analysis import roofline as rf                       # noqa: E402
from repro.common.config import INPUT_SHAPES                    # noqa: E402
from repro.configs import ARCH_IDS, get_config                  # noqa: E402
from repro.launch import plans as plans_mod                     # noqa: E402
from repro.launch.specs import build_programs                   # noqa: E402

OUT_ROOT = os.environ.get("REPRO_DRYRUN_DIR", "experiments/dryrun")


def run_cell(arch: str, shape_name: str, *, multi_pod: bool, force: bool = False,
             gossip_variant: bool = True) -> list:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    outdir = os.path.join(OUT_ROOT, mesh_name)
    os.makedirs(outdir, exist_ok=True)
    plan = plans_mod.make_plan(arch, shape_name)
    cfg = get_config(arch)
    chips = plans_mod.mesh_config(plan, multi_pod=multi_pod).num_chips
    results = []
    for prog in build_programs(arch, shape_name, multi_pod=multi_pod,
                               gossip_variant=gossip_variant):
        path = os.path.join(outdir, f"{arch}__{shape_name}__{prog.name}.json")
        if os.path.exists(path) and not force:
            with open(path) as f:
                results.append(json.load(f))
            print(f"[skip] {mesh_name} {arch} {shape_name} {prog.name} (cached)")
            continue
        t0 = time.time()
        try:
            if prog.mesh is not None:
                with jax.set_mesh(prog.mesh):
                    lowered = prog.jitted.lower(*prog.args)
            else:
                lowered = prog.jitted.lower(*prog.args)
            compiled = lowered.compile()
            mem = compiled.memory_analysis()
            cost = compiled.cost_analysis() or {}
            hlo_text = compiled.as_text()
            roof = rf.analyze_program(arch, plan.shape, prog.name, hlo_text, cfg, chips,
                                      peak_memory=getattr(mem, "temp_size_in_bytes", None))
            rec = roof.to_dict()
            rec.update({
                "mesh": mesh_name,
                "status": "ok",
                "compile_seconds": time.time() - t0,
                "plan": {"workers_per_pod": plan.workers_per_pod,
                         "grad_accum": plan.grad_accum,
                         "decode_window": plan.decode_window,
                         "notes": plan.notes},
                "memory_analysis": {
                    k: int(getattr(mem, k)) for k in
                    ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes")
                    if hasattr(mem, k)},
                "xla_cost_analysis_flops_bodyonce": float(cost.get("flops", 0.0)) if cost else None,
            })
            print(f"[ok]   {mesh_name} {arch} {shape_name} {prog.name} "
                  f"({rec['compile_seconds']:.1f}s, bottleneck={rec['bottleneck']})")
        except Exception as e:  # noqa: BLE001 — a failing cell is a bug to record, not hide
            rec = {"mesh": mesh_name, "arch": arch, "shape": shape_name,
                   "program": prog.name, "status": "error",
                   "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc(),
                   "compile_seconds": time.time() - t0}
            print(f"[FAIL] {mesh_name} {arch} {shape_name} {prog.name}: {e}")
        with open(path, "w") as f:
            json.dump(rec, f, indent=2)
        results.append(rec)
    return results


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCH_IDS, default=None)
    ap.add_argument("--shape", choices=tuple(INPUT_SHAPES), default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--no-gossip-variant", action="store_true")
    args = ap.parse_args()

    assert len(jax.devices()) == 512, "dryrun must own the 512 placeholder devices"
    archs = [args.arch] if args.arch else list(ARCH_IDS)
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    if not (args.all or args.arch or args.shape):
        ap.error("pass --all or --arch/--shape")
    failures = 0
    for multi_pod in meshes:
        for arch in archs:
            for shape in shapes:
                recs = run_cell(arch, shape, multi_pod=multi_pod, force=args.force,
                                gossip_variant=not args.no_gossip_variant)
                failures += sum(r.get("status") != "ok" for r in recs)
    print(f"done; failures={failures}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
