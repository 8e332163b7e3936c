"""What every loop of the benchmark shares: the cell as ``BENCHMARK.json``
names it, the look for the chip, spans and compile counts, percentiles,
the per-layer readers and the result line.

Everything that belongs to one configuration, traffic mix, metric or
kernel is a file of its own, found by its name:

    bench/configs/<config>.json     sizes and run settings of a model
    bench/traffic/<mix>.json        parameters of a traffic mix; its
                                    ``loop`` names bench/loops/<loop>.py
    bench/limits/<workload>.json    the limits ``correct`` is judged by
    bench/metrics/<metric>.py       one per-layer metric: read(run)
    bench/flops/<kernel>.py         operations and bytes of one kernel call
    bench/references/<family>.py    plain float32 reference of a family
    bench/adapters/<family>.py      the family's weights in the program's
                                    layout, and its ModelConfig

Nothing here imports JAX at module level, so the CPU tests collect
without touching a device.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


# ---------------- the cell ----------------

@dataclasses.dataclass
class Cell:
    workload: dict          # the BENCHMARK.json entry
    config: dict            # bench/configs/<config>.json
    traffic: dict           # bench/traffic/<mix>.json
    limits: dict            # bench/limits/<workload>.json
    end_to_end: list        # metric entries this cell reports
    per_layer: list
    seed: int = 0
    seconds: float = 10.0
    trace: bool = False

    @property
    def name(self) -> str:
        return self.workload["name"]


def _reports(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def load_cell(workload: str, spec: dict | None = None, **run) -> Cell:
    """The cell ``workload`` of ``BENCHMARK.json`` with its files; the
    window is the spec's ``run_seconds`` unless ``seconds`` is given."""
    spec = spec or load_spec()
    if run.get("seconds") is None:
        run["seconds"] = float(spec["run_seconds"])
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; have "
                         f"{sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    return Cell(
        workload=w,
        config=json.loads((ROOT / conf["file"]).read_text()),
        traffic=json.loads((BENCH / "traffic" /
                            f"{w['traffic']}.json").read_text()),
        limits=json.loads((BENCH / "limits" /
                           f"{workload}.json").read_text()),
        end_to_end=[m for m in spec["end_to_end"] if _reports(m, workload)],
        per_layer=[m for m in spec["per_layer"] if _reports(m, workload)],
        **run)


def load_by_name(kind: str, name: str):
    """Import bench/<kind>/<name>.py (names may hold dots)."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"no {kind} file {path.relative_to(ROOT)}")
    mod_name = f"bench_{kind}_{name}".replace(".", "_").replace("-", "_")
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


# ---------------- the chip ----------------

def require_chips(count: int):
    """The TPU devices, or exit non-zero, printing no result."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"bench: JAX found no TPU (platform {devs[0].platform!r}); "
                 "the benchmark measures the chip and runs nowhere else")
    if len(devs) < count:
        sys.exit(f"bench: the cell needs {count} TPU chips, found "
                 f"{len(devs)}")
    return devs[:count]


def use_program(root: Path = ROOT) -> str:
    """Put the program on the path and turn its compile cache on; returns
    the cache directory.  Small programs are cached too, so a second run
    of a cell compiles nothing."""
    src = str(root / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def program_weights(conf: dict, mcfg, seed: int):
    """The configuration's weights from the seed, made on the device in
    one jitted call in the type the configuration runs, renamed into the
    program's tree; checked against the tree the program would build."""
    import jax
    import jax.numpy as jnp

    from repro.models.transformer import init_lm
    ref = load_by_name("references", conf["family"])
    ad = load_by_name("adapters", conf["family"])
    dtype = jnp.dtype(conf["run"]["dtype"])
    params = jax.jit(lambda k: ad.to_program(
        ref.init_weights(conf, k, dtype)))(jax_key(seed))
    want = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), mcfg,
                                          dtype))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if want != got:
        raise SystemExit("bench: the adapter's weights do not match the "
                         f"program's tree:\n{got}\n!=\n{want}")
    return params


def jax_key(seed: int):
    """A JAX key from any whole-number seed (the driver's exceed 32 bits)."""
    import jax
    import numpy as np
    return jax.random.PRNGKey(int(np.random.default_rng(seed)
                                  .integers(0, 2 ** 31 - 1)))


class CompileClock:
    """Seconds and count of XLA compilations (or persistent-cache reads)
    since construction, from JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1


class Spans:
    """Host spans around the benchmark's calls into the program, on
    ``time.perf_counter``.  While a trace is on, each span is also a
    profiler annotation, so the trace holds it on the device's clock."""

    def __init__(self):
        self.items: list[tuple[str, float, float]] = []
        self.annotate = False

    def span(self, name: str):
        return _Span(self, name)

    def of(self, name: str, lo: float = -math.inf, hi: float = math.inf):
        return [(a, b) for n, a, b in self.items
                if n == name and a >= lo and b <= hi]


class _Span:
    def __init__(self, owner: Spans, name: str):
        self.owner, self.name = owner, name
        self.ann = None

    def __enter__(self):
        if self.owner.annotate:
            import jax
            self.ann = jax.profiler.TraceAnnotation(self.name)
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.t1 = time.perf_counter()
        if self.ann is not None:
            self.ann.__exit__(*exc)
        self.owner.items.append((self.name, self.t0, self.t1))
        return False


class Profiler:
    """A device trace of the measured window, read and deleted at once.

    Python's own tracer is off: it would slow the host loop that the
    window measures and swell the file."""

    def __init__(self, spans: Spans):
        self.spans, self.dir = spans, None

    def start(self) -> None:
        import tempfile

        import jax
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.spans.annotate = True

    def stop(self) -> dict:
        import shutil

        import jax

        from bench import trace
        self.spans.annotate = False
        jax.profiler.stop_trace()
        try:
            return trace.load(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


# ---------------- numbers ----------------

def percentile(values, q: float) -> float | None:
    """The ``q``-th percentile, linear between order statistics."""
    v = sorted(values)
    if not v:
        return None
    pos = (len(v) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


@dataclasses.dataclass
class Run:
    """What the per-layer readers read: host spans of the window, the
    program's counters, kernel calls with their shapes, the reduced
    trace, and the model's sizes."""
    spans: Spans
    window: tuple[float, float]
    counters: dict
    calls: dict                 # kernel -> list of per-layer call shapes
    dims: dict                  # heads, kv_heads, head_dim, layers, itemsize
    n_active: dict              # matmul parameters: {"body", "head"}
    device_kind: str
    trace: dict | None = None
    extra: dict = dataclasses.field(default_factory=dict)

    def step_spans(self, name: str):
        lo, hi = self.window
        return self.spans.of(name, lo, hi)


def kernel_roofline(run: Run, kernel: str) -> float | None:
    """Least time of the kernel's calls in the window over its device
    time there, in %.  Nothing to read: None."""
    from bench import peaks, trace
    calls = run.calls.get(kernel) or []
    if run.trace is None or not calls:
        return None
    fl = load_by_name("flops", kernel)
    flops = moved = 0.0
    for c in calls:
        f, b = fl.cost(c, run.dims)
        flops, moved = flops + f, moved + b
    layers = run.dims["layers"]
    t_min, _ = peaks.least_time(flops * layers, moved * layers,
                                run.device_kind)
    t_dev, n = trace.kernel_seconds(run.trace, fl.MATCH)
    if n == 0 or t_dev <= 0:
        return None
    return 100.0 * t_min / t_dev


def per_layer(cell: Cell, run: Run) -> dict:
    out = {}
    for m in cell.per_layer:
        v = load_by_name("metrics", m["name"]).read(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def device_info(devs, trace_rec: dict | None = None) -> dict:
    stats = [d.memory_stats() or {} for d in devs]
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": max(int(s.get("peak_bytes_in_use", 0))
                                     for s in stats)}
    if trace_rec is not None:
        from bench import trace
        info["busy_s"] = trace.busy_s(trace_rec)
        info["window_s"] = trace.window_s(trace_rec)
    return info


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def result(cell: Cell, *, correct: bool, attempted: int, failed: int,
           end_to_end: dict, run: Run, device: dict,
           compared: dict) -> dict:
    """The result line.  ``compared`` maps each number ``correct`` was
    judged by to {"value", "limit"}; it is the last key of the line, and
    the entry point prints it last on standard error too."""
    from bench import trace
    if cell.trace:
        metrics = per_layer(cell, run)
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        metrics = {k: {"value": float(v), "unit": units[k]}
                   for k, v in end_to_end.items()
                   if k in units and v is not None}
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if cell.trace and run.trace is not None:
        out["breakdown"] = trace.breakdown(run.trace)
    out["compared"] = compared
    return out
