"""Trip-count-aware static analysis of compiled (post-SPMD) HLO.

Why not compiled.cost_analysis()?  Measured on this toolchain: XLA's cost
analysis counts every while-loop BODY ONCE — a scan over 24 periods
reports 1/24th of its real flops (verified: scan x10 of a matmul reports
exactly 1 matmul).  Our programs are scans-over-scans (periods x flash
blocks x CE chunks), so raw cost_analysis under-counts ~20-100x and, worse,
*differently per cell*, which would make every roofline comparison wrong.
The same bug hits a naive HLO-text grep for collectives: FSDP all-gathers
live inside the period loop body.

So we parse the HLO module into its computations and walk ENTRY
recursively:

  flops   : every `dot` contributes 2 * |out| * contracted_size
            (shapes resolved via a per-computation SSA symbol table)
  bytes   : HBM-traffic model at FUSION boundaries — a fusion (or bare
            non-free op) reads its operands and writes its result once;
            internal fusion ops are VMEM-resident and free
  colls   : result bytes of all-gather / all-reduce / reduce-scatter /
            all-to-all / collective-permute, ring-weighted:
            all-reduce 2x, others 1x  (n->inf limit of (n-1)/n factors)
  whiles  : body+cond costs multiplied by the trip count extracted from
            the condition's ROOT compare-vs-constant (all our loops are
            static scans); conditionals take the max branch

All sums are per-chip: the module analyzed is the per-device SPMD program.
"""
from __future__ import annotations

import re
from typing import Any

_BYTES = {"f64": 8, "s64": 8, "u64": 8, "c64": 8, "f32": 4, "s32": 4,
          "u32": 4, "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
          "f8e4m3fn": 1, "f8e5m2": 1, "s8": 1, "u8": 1, "pred": 1}

_COLL_FACTOR = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
                "all-to-all": 1.0, "collective-permute": 1.0}

# ops that move no HBM bytes of their own.  NOTE: custom-call is NOT
# free — on TPU every pallas_call lowers to one, and its kernel streams
# all operands in and the result out of HBM exactly once (that is the
# whole point of a flash kernel).  It used to sit in this set, which
# silently zeroed the HBM bytes of precisely the kernels this module
# exists to price; cost() now charges it operands + result.
_FREE = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast",
         "after-all", "partition-id", "replica-id",
         "bitcast-convert", "opt-barrier"}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?(%[\w.\-]+)\s*=\s*(.*?)\s*"
                     r"([\w\-]+)\(")
_COMP_HDR = re.compile(r"^(?:ENTRY\s+)?(%?[\w.\-]+)\s*\(")
_CALLS_RE = re.compile(r"(?:calls|to_apply|body)=(%[\w.\-]+)")
_COND_RE = re.compile(r"condition=(%[\w.\-]+)")
_BRANCH_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_CONTR_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_BATCH_RE = re.compile(r"lhs_batch_dims=\{([\d,]*)\}")


def _shape_elems_bytes(txt: str) -> tuple[int, int]:
    """Total (elements, bytes) over every dtype[dims] token in txt."""
    el, by = 0, 0
    for dt, dims in _SHAPE_RE.findall(txt):
        if dt not in _BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        el += n
        by += n * _BYTES[dt]
    return el, by


def _first_shape_dims(txt: str):
    m = _SHAPE_RE.search(txt)
    if not m:
        return None
    return [int(d) for d in m.group(2).split(",") if d]


class HloProgram:
    def __init__(self, hlo_text: str):
        self.comps: dict[str, list[str]] = {}
        self.entry: str | None = None
        cur, buf = None, []
        for line in hlo_text.splitlines():
            if line.endswith("{") and ("->" in line):
                m = _COMP_HDR.match(line.strip())
                if m:
                    cur = m.group(1)
                    if not cur.startswith("%"):
                        cur = "%" + cur
                    buf = []
                    if line.strip().startswith("ENTRY"):
                        self.entry = cur
                    continue
            if line.strip() == "}":
                if cur is not None:
                    self.comps[cur] = buf
                cur = None
                continue
            if cur is not None:
                buf.append(line)
        self._memo: dict[str, dict[str, float]] = {}

    # ---------------- per-computation symbol table ----------------

    def _symbols(self, comp: str) -> dict[str, str]:
        table = {}
        for line in self.comps.get(comp, ()):
            m = _DEF_RE.match(line)
            if m:
                table[m.group(1)] = m.group(2)      # name -> result shape txt
        return table

    # ---------------- trip counts ----------------

    def trip_count(self, cond_comp: str) -> int:
        """Loop bound from the condition computation: the largest integer
        constant in it (and in computations it calls — the compare is often
        wrapped in a fusion).  All our loops are 0..N step-1 scans."""
        best = 1
        stack, seen = [cond_comp], set()
        while stack:
            comp = stack.pop()
            if comp in seen:
                continue
            seen.add(comp)
            for line in self.comps.get(comp, ()):
                for n in re.findall(r"constant\((\d+)\)", line):
                    best = max(best, int(n))
                c = _CALLS_RE.search(line)
                if c:
                    stack.append(c.group(1))
        return best

    # ---------------- recursive cost walk ----------------

    def cost(self, comp: str | None = None) -> dict[str, float]:
        comp = comp or self.entry
        if comp in self._memo:
            return self._memo[comp]
        tot = {"flops": 0.0, "bytes": 0.0, "coll_bytes": 0.0,
               "coll_wire": 0.0, "coll_count": 0.0}
        by_op: dict[str, float] = {}
        table = self._symbols(comp)
        self._memo[comp] = tot                     # cycle guard
        for line in self.comps.get(comp, ()):
            m = _DEF_RE.match(line)
            if not m:
                continue
            _, result_txt, op = m.groups()
            base = op.replace("-start", "").replace("-done", "")
            # ---- nested computations ----
            if op == "while":
                cond = _COND_RE.search(line)
                body = _CALLS_RE.search(line)
                trips = self.trip_count(cond.group(1)) if cond else 1
                if body:
                    sub = self.cost(body.group(1))
                    for k in tot:
                        tot[k] += trips * sub[k]
                continue
            if op == "conditional":
                br = _BRANCH_RE.search(line)
                if br:
                    subs = [self.cost(b.strip()) for b in
                            br.group(1).split(",")]
                    for k in tot:
                        tot[k] += max(s[k] for s in subs)
                continue
            if op in ("fusion", "call", "map", "reduce", "reduce-window",
                      "sort", "scatter", "select-and-scatter"):
                c = _CALLS_RE.search(line)
                if c and c.group(1) in self.comps:
                    sub = self.cost(c.group(1))
                    tot["flops"] += sub["flops"]   # fused dots still compute
                    tot["coll_bytes"] += sub["coll_bytes"]
                    tot["coll_wire"] += sub["coll_wire"]
                    tot["coll_count"] += sub["coll_count"]
                tot["bytes"] += self._traffic(line, result_txt, table)
                continue
            # ---- leaf ops ----
            if base in _COLL_FACTOR:
                _, rb = _shape_elems_bytes(result_txt)
                if op.endswith("-done"):
                    continue                        # counted at -start
                tot["coll_bytes"] += rb
                tot["coll_wire"] += rb * _COLL_FACTOR[base]
                tot["coll_count"] += 1
                tot["bytes"] += 2 * rb              # HBM read+write around wire
                continue
            if op == "dot":
                out_dims = _first_shape_dims(result_txt) or []
                out_n = 1
                for d in out_dims:
                    out_n *= d
                lhs = self._operand_shapes(line, table)
                contr = _CONTR_RE.search(line)
                csize = 1
                if lhs and contr:
                    ldims = lhs[0]
                    for i in (int(x) for x in contr.group(1).split(",") if x):
                        if i < len(ldims):
                            csize *= ldims[i]
                tot["flops"] += 2.0 * out_n * csize
                tot["bytes"] += self._traffic(line, result_txt, table)
                continue
            if op == "convolution":
                # rough: 2 * out * (kernel elems) — none perf-critical here
                out_dims = _first_shape_dims(result_txt) or []
                out_n = 1
                for d in out_dims:
                    out_n *= d
                tot["flops"] += 2.0 * out_n
                continue
            if base == "custom-call":
                # a pallas_call kernel: reads every operand, writes the
                # result, once each (the -done half of an async pair is
                # the same transfer, already charged at -start)
                if op.endswith("-done"):
                    continue
                _, rb = _shape_elems_bytes(result_txt)
                tot["bytes"] += rb + float(
                    sum(self._operand_sizes(line, table)))
                continue
            if op in _FREE:
                continue
            tot["bytes"] += self._traffic(line, result_txt, table)
        self._memo[comp] = tot
        return tot

    def _traffic(self, line: str, result_txt: str,
                 table: dict[str, str]) -> float:
        """HBM traffic model for one (possibly fused) op, calibrated to TPU
        fusion behaviour (the CPU-lowered HLO we analyze fuses *less* than
        TPU would, so charging operand reads on every op overcounts ~10x):

        * dynamic-update-slice: the big buffer aliases in place — traffic
          is 2x the update slice (read + write), not the buffer.
        * dot / reduce: stream all operands + result exactly once.
        * copy: read + write.
        * gather / scatter / dynamic-slice: touch ~result-sized windows of
          their operands, not whole operands.
        * anything else (elementwise chains, converts, broadcasts, selects
          — whether CPU fused them or not): ONE result write.  Their reads
          are the producing ops' writes, already charged there; on TPU
          these chains fuse into neighbours and never re-read HBM.
        """
        _, rb = _shape_elems_bytes(result_txt)
        ops = self._operand_sizes(line, table)
        if "dynamic-update-slice" in line:
            return 2.0 * (min(ops) if ops else rb)
        if re.search(r"\s(dot|reduce|reduce-window)\(", line):
            return rb + float(sum(ops))
        if re.search(r"\scopy\(", line):
            return 2.0 * rb
        if re.search(r"\s(gather|scatter|dynamic-slice)\(", line):
            return rb + min(float(sum(ops)), 2.0 * rb)
        return float(rb)

    def _operand_shapes(self, line: str, table: dict[str, str]):
        call = line[line.index("("):]
        shapes = []
        for name in re.findall(r"(%[\w.\-]+)", call):
            if name in table:
                dims = _first_shape_dims(table[name])
                if dims is not None:
                    shapes.append(dims)
        return shapes

    def _operand_sizes(self, line: str, table: dict[str, str]) -> list[int]:
        call = line[line.index("("):]
        seen, sizes = set(), []
        for name in re.findall(r"(%[\w.\-]+)", call):
            if name in table and name not in seen:
                seen.add(name)
                _, b = _shape_elems_bytes(table[name])
                sizes.append(b)
        return sizes


def collective_result_bytes(hlo_text: str, op: str = "all-gather"
                            ) -> list[int]:
    """Result bytes of every ``op`` instruction in the module, across ALL
    computations (loop bodies included, unweighted — callers that need
    trip counts use :meth:`HloProgram.cost`).  Async pairs count once, at
    the -start half.  This is the shared walker behind the mesh-safety
    pass in ``repro.analysis``: a post-SPMD all-gather whose result is
    the full KV cache is the per-chip HBM blowup that pass hunts."""
    prog = HloProgram(hlo_text)
    sizes = []
    for lines in prog.comps.values():
        for line in lines:
            m = _DEF_RE.match(line)
            if not m:
                continue
            _, result_txt, found = m.groups()
            if found.endswith("-done"):
                continue
            if found.replace("-start", "") != op:
                continue
            _, rb = _shape_elems_bytes(result_txt)
            sizes.append(rb)
    return sizes


def analyze_hlo(hlo_text: str) -> dict[str, float]:
    prog = HloProgram(hlo_text)
    c = prog.cost()
    return {"flops": c["flops"], "bytes_accessed": c["bytes"],
            "collective_bytes": c["coll_bytes"],
            "collective_wire_bytes": c["coll_wire"],
            "collective_count": c["coll_count"]}


# ---------------- roofline ----------------

# Published per-chip peaks, keyed by jax's ``device_kind``.  Source:
# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect (= 200e9 bytes/s).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 1600e9 / 8},
}


def device_peaks(device_kind: str) -> dict[str, float]:
    """Peak FLOP/s and bytes/s of one chip of ``device_kind``; a kind
    missing from :data:`PEAKS` is an error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; have {sorted(PEAKS)}") from None


def roofline_terms(flops: float, bytes_accessed: float, wire_bytes: float,
                   device_kind: str) -> dict[str, Any]:
    """Three per-chip time lower bounds on a ``device_kind`` chip, s."""
    peak = device_peaks(device_kind)
    t_c = flops / peak["flops"]
    t_m = bytes_accessed / peak["hbm_bw"]
    t_n = wire_bytes / peak["ici_bw"]
    dom = max(("compute", t_c), ("memory", t_m), ("collective", t_n),
              key=lambda kv: kv[1])
    return {"t_compute": t_c, "t_memory": t_m, "t_collective": t_n,
            "bottleneck": dom[0], "t_bound": dom[1]}


def cpu_upcast_correction(hlo_text: str, param_shapes) -> float:
    """Bytes of f32 temp copies of bf16 parameters — a CPU-backend
    artifact (XLA CPU upcasts bf16 dot operands to f32; the TPU MXU eats
    bf16 natively, so these buffers do not exist on the target).  Counted
    as: one f32 buffer per distinct parameter shape that appears as an
    f32 tensor in the HLO.  Shapes are matched on normalized dims
    (singletons dropped, sorted) so transposed / singleton-expanded
    weight copies are caught too."""
    def norm(dims):
        return tuple(sorted(d for d in dims if d != 1))

    want = {}
    for shp in param_shapes:
        if len(shp) == 0 or np_prod(shp) < (1 << 16):
            continue                        # small params: noise
        want[norm(shp)] = 4.0 * float(int(np_prod(shp)))
    seen = set()
    total = 0.0
    for m in re.finditer(r"f32\[([\d,]+)\]", hlo_text):
        key = norm(int(d) for d in m.group(1).split(","))
        if key in want and key not in seen:
            seen.add(key)
            total += want[key]
    return total


def np_prod(shape) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n


def cost_summary(compiled) -> dict[str, float]:
    """Raw XLA cost_analysis (per-device, while-bodies-once) — kept for
    cross-checking the HLO walk, not for the roofline."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        ca = None
    if isinstance(ca, list):
        ca = ca[0] if ca else {}
    ca = ca or {}
    return {"xla_flops_once": float(ca.get("flops", 0.0)),
            "xla_bytes_once": float(ca.get("bytes accessed", 0.0))}


def memory_summary(compiled) -> dict[str, float]:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        ma = None
    if ma is None:
        return {}
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = float(v)
    if out:
        out["total_hbm_bytes"] = (out.get("argument_size_in_bytes", 0.0)
                                  + out.get("output_size_in_bytes", 0.0)
                                  + out.get("temp_size_in_bytes", 0.0)
                                  - out.get("alias_size_in_bytes", 0.0))
    return out
