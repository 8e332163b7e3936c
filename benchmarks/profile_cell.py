"""Dry-run profiler: top collectives + top tensors for one cell.

    PYTHONPATH=src python -m benchmarks.profile_cell --arch qwen3-14b \
        --shape train_4k

This is the §Perf microscope: it attributes trip-count-weighted wire
bytes to individual collective ops (with their tensor shapes) so each
hillclimb hypothesis targets the actual dominant transfer.
"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))
import argparse
import collections
import re

import jax  # noqa: E402

from repro.launch import hlo_analysis as H  # noqa: E402
from repro.launch.cells import build_cell  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402


def profile(arch: str, shape: str, multi_pod: bool = False, top: int = 12):
    mesh = make_production_mesh(multi_pod=multi_pod)
    cell = build_cell(arch, shape, mesh)
    with jax.set_mesh(mesh):
        compiled = cell.lower().compile()
    txt = compiled.as_text()
    p = H.HloProgram(txt)
    coll = collections.Counter()
    ops_bytes = collections.Counter()

    def walk(comp, mult):
        for line in p.comps.get(comp, ()):
            m = H._DEF_RE.match(line)
            if not m:
                continue
            _, rt, op = m.groups()
            if op == "while":
                c = H._COND_RE.search(line)
                b = H._CALLS_RE.search(line)
                t = p.trip_count(c.group(1)) if c else 1
                if b:
                    walk(b.group(1), mult * t)
                continue
            base = op.replace("-start", "").replace("-done", "")
            if base in H._COLL_FACTOR and not op.endswith("-done"):
                _, rb = H._shape_elems_bytes(rt)
                meta = re.search(r'op_name="([^"]*)"', line)
                tag = (meta.group(1).split("/")[-1][:48] if meta else "?")
                coll[f"{base:20s} {rt[:48]:50s} {tag}"] += \
                    mult * rb * H._COLL_FACTOR[base]
            cc = H._CALLS_RE.search(line)
            if op in ("fusion", "call") and cc and cc.group(1) in p.comps:
                walk(cc.group(1), mult)

    walk(p.entry, 1)
    print(f"== {arch} {shape} {'multi' if multi_pod else 'single'} — "
          f"top collectives (wire bytes/chip) ==")
    total = sum(coll.values())
    for k, v in coll.most_common(top):
        print(f"{v/1e9:9.2f} GB ({v/max(total,1)*100:4.1f}%)  {k}")
    print(f"{total/1e9:9.2f} GB TOTAL -> t_n = {total/50e9:.2f} s")
    return coll


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()
    profile(args.arch, args.shape, args.multi_pod)


if __name__ == "__main__":
    main()
