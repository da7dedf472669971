import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
# CPU only, for this process and the per-cell children it starts: a
# parent holding a TPU would leave its children no chip to start on
os.environ["JAX_PLATFORMS"] = "cpu"
"""Multi-pod dry-run: lower + compile every (architecture x input shape)
on the production meshes, record memory/cost analyses + roofline terms.

MUST be run as a script/module (the XLA_FLAGS line above executes before
any jax import).  One cell:

    PYTHONPATH=src python -m repro.launch.dryrun \
        --arch stablelm-12b --shape decode_32k --mesh single

Full sweep (subprocess per cell so device/compile state can't leak):

    PYTHONPATH=src python -m repro.launch.dryrun --all --out results/dryrun
"""

import argparse
import json
import subprocess
import sys
import time


def run_cell(arch: str, shape_name: str, multi_pod: bool) -> dict:
    from repro.configs import SHAPES, entry, get
    from repro.launch import roofline, steps
    from repro.launch.mesh import make_production_mesh

    cfg = get(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    mesh_name = "2x16x16" if multi_pod else "16x16"

    t0 = time.time()
    fn, args = steps.build(cfg, shape, mesh)
    with mesh:
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    # persist the optimized HLO so analysis iterations don't recompile
    from repro import compression
    os.makedirs("results/hlo", exist_ok=True)
    hlo_path = (f"results/hlo/{arch}__{shape_name}__"
                f"{'multi' if multi_pod else 'single'}.hlo.zst")
    with open(hlo_path, "wb") as f:
        f.write(compression.compress(compiled.as_text().encode(), level=9))

    mem = compiled.memory_analysis()
    print(f"== {arch} x {shape_name} on {mesh_name} ==")
    print("memory_analysis:", mem)
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    print("cost_analysis: flops=%.3e bytes=%.3e"
          % (cost.get("flops", 0), cost.get("bytes accessed", 0)))

    n_params = cfg.param_count()
    n_active = cfg.active_param_count()
    rl = roofline.extract(
        compiled, arch=arch, shape=shape_name, mesh_name=mesh_name,
        chips=chips, cfg=cfg, shape_spec=shape, n_params=n_params,
        n_active=n_active)
    rec = rl.to_dict()
    rec.update(
        lower_s=t_lower, compile_s=t_compile, chips=chips,
        n_params=n_params, n_active=n_active,
        argument_size=getattr(mem, "argument_size_in_bytes", None),
        output_size=getattr(mem, "output_size_in_bytes", None),
        temp_size=getattr(mem, "temp_size_in_bytes", None),
        generated_code_size=getattr(mem, "generated_code_size_in_bytes",
                                    None),
    )
    print("roofline: compute=%.4fs memory=%.4fs collective=%.4fs "
          "dominant=%s useful=%.2f frac=%.3f"
          % (rl.compute_s, rl.memory_s, rl.collective_s, rl.dominant,
             rl.useful_flops_ratio, rl.roofline_fraction))
    return rec


def all_cells():
    from repro.configs import SHAPES, entry, names
    for arch in names():
        if arch == "llama-1.5b":
            continue  # paper's own model, not an assigned cell
        e = entry(arch)
        for shape in e.shapes:
            yield arch, shape


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--timeout", type=int, default=1800)
    args = ap.parse_args()

    if args.all:
        os.makedirs(args.out, exist_ok=True)
        cells = [(a, s, m)
                 for a, s in all_cells()
                 for m in (("single", "multi") if args.mesh == "both"
                           else (args.mesh,))]
        failures = []
        for arch, shape, meshk in cells:
            path = os.path.join(args.out, f"{arch}__{shape}__{meshk}.json")
            if os.path.exists(path):
                print("skip (cached):", path)
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--mesh", meshk,
                   "--out", args.out]
            print(">>", " ".join(cmd), flush=True)
            r = subprocess.run(cmd, timeout=args.timeout,
                               env={**os.environ, "JAX_PLATFORMS": "cpu"})
            if r.returncode != 0:
                failures.append((arch, shape, meshk))
                print("!! FAILED", arch, shape, meshk, flush=True)
        print("failures:", failures)
        sys.exit(1 if failures else 0)

    rec = run_cell(args.arch, args.shape, args.mesh == "multi")
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(
        args.out, f"{args.arch}__{args.shape}__"
        f"{'multi' if args.mesh == 'multi' else 'single'}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1, default=str)
    print("wrote", path)


if __name__ == "__main__":
    main()
