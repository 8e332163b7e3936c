import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))
# ^ MUST precede any jax import: jax locks the device count at first init.
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell and
extract the roofline terms from the compiled artifact.

Usage:
    python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k
    python -m repro.launch.dryrun --all --mesh both --out benchmarks/artifacts/dryrun

Success criteria (assignment): .lower().compile() succeeds on the 16x16
single-pod mesh AND the 2x16x16 multi-pod mesh for every applicable cell;
memory_analysis proves residency, cost_analysis + HLO collective parse
feed EXPERIMENTS.md §Roofline.
"""
import argparse
import json
import time
import traceback

import jax

from repro.configs import registry
from repro.launch import hlo_analysis as ha
from repro.launch.cells import applicable_cells, build_cell
from repro.launch.mesh import make_production_mesh
from repro.models.accounting import analytic_model_flops


def run_cell(arch: str, shape: str, multi_pod: bool, *, verbose: bool = True
             ) -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec = {"arch": arch, "shape": shape, "mesh": mesh_name, "ok": False}
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        cell = build_cell(arch, shape, mesh)
        rec["kind"] = cell.kind
        t0 = time.perf_counter()
        with jax.set_mesh(mesh):
            lowered = cell.lower()
            rec["lower_s"] = round(time.perf_counter() - t0, 2)
            t1 = time.perf_counter()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.perf_counter() - t1, 2)
        rec["xla_cost_once"] = ha.cost_summary(compiled)   # cross-check only
        rec["memory"] = ha.memory_summary(compiled)
        hlo_text = compiled.as_text()
        # CPU-only bf16->f32 weight-upcast temps (absent on TPU)
        params_tree = (cell.args[0].params if cell.kind == "train"
                       else cell.args[0])
        pshapes = [l.shape for l in jax.tree.leaves(params_tree)]
        corr = ha.cpu_upcast_correction(hlo_text, pshapes)
        rec["memory"]["cpu_upcast_bytes"] = corr
        rec["memory"]["tpu_hbm_bytes"] = max(
            rec["memory"].get("total_hbm_bytes", 0.0) - corr, 0.0)
        # hardware-true resident state from the declared shardings; the
        # fit check adds a 2 GiB working-set allowance for activations
        resident = cell.resident_bytes_per_chip()
        rec["memory"]["resident_bytes_per_chip"] = resident
        rec["memory"]["fits_v5e_16g"] = resident + 2 * 2**30 < 16e9
        a = ha.analyze_hlo(hlo_text)                       # trip-count-aware
        rec.update(a)
        # the production mesh is a v5e pod; the CPU emulation only lowers
        rec["roofline"] = ha.roofline_terms(
            a["flops"], a["bytes_accessed"], a["collective_wire_bytes"],
            device_kind="TPU v5 lite")
        n_dev = mesh.devices.size
        mf = analytic_model_flops(cell.cfg, cell.cell)
        rec["model_flops_global"] = mf
        rec["useful_ratio"] = (mf / (a["flops"] * n_dev)
                               if a["flops"] else 0.0)
        rec["ok"] = True
        if verbose:
            r = rec["roofline"]
            mem = rec["memory"].get("resident_bytes_per_chip", 0) / 2**30
            print(f"[dryrun] {arch:22s} {shape:12s} {mesh_name:10s} OK "
                  f"lower={rec['lower_s']}s compile={rec['compile_s']}s "
                  f"resident={mem:.2f}GiB "
                  f"tc={r['t_compute']:.3e} tm={r['t_memory']:.3e} "
                  f"tn={r['t_collective']:.3e} -> {r['bottleneck']} "
                  f"useful={rec['useful_ratio']:.2f}",
                  flush=True)
    except Exception as e:  # noqa: BLE001 — sweep must survive cell bugs
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        if verbose:
            print(f"[dryrun] {arch:22s} {shape:12s} {mesh_name:10s} "
                  f"FAIL {rec['error']}", flush=True)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="benchmarks/artifacts/dryrun")
    args = ap.parse_args()

    assert len(jax.devices()) == 512, "XLA host-device override failed"
    archs = registry.ARCH_IDS if (args.all or not args.arch) else [args.arch]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    os.makedirs(args.out, exist_ok=True)
    results = []
    for arch in archs:
        shapes = ([args.shape] if args.shape else applicable_cells(arch))
        for shape in shapes:
            for mp in meshes:
                rec = run_cell(arch, shape, mp)
                results.append(rec)
                tag = f"{arch}__{shape}__{'multi' if mp else 'single'}"
                with open(os.path.join(args.out, tag + ".json"), "w") as f:
                    json.dump(rec, f, indent=1)
    n_ok = sum(r["ok"] for r in results)
    print(f"[dryrun] {n_ok}/{len(results)} cells OK")
    if n_ok < len(results):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
