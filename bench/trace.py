"""Reduction of a JAX profiler trace to the numbers the metrics read.

A trace is read once, by :func:`load`, into a plain record::

    {"device_ops": [[chip, name, start_ns, end_ns], ...],
     "spans":      [[name, start_ns, end_ns], ...],
     "window":     [start_ns, end_ns], "chips": n}

``device_ops`` are the events of the device planes' op line only (host
threads are dropped), named by their HLO instruction (``copy.109``,
``_flash_decode_paged_jit.11``: a Pallas kernel's custom call carries the
kernel's name).  The op line nests: a ``while`` holds the ops of its
body.  ``spans`` are the host annotations the benchmark itself wrote
around its calls into the program (``bench.*``, ``engine.step``,
``trainer.step``), on the trace's clock.  Everything below works on that
record, so the reduction is checked on a small recorded copy without a
chip.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = "/device:TPU:"
OP_LINE = "XLA Ops"
SPAN_PREFIXES = ("bench.", "engine.", "trainer.")
WINDOW_SPAN = "bench.window"      # the measured window, written by the loop


def load(log_dir: str) -> dict:
    """Read the ``.xplane.pb`` under ``log_dir`` into the plain record."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(files) != 1:
        raise RuntimeError(f"expected one xplane file under {log_dir}, "
                           f"found {files}")
    prof = ProfileData.from_file(files[0])
    ops, spans, chips = [], [], set()
    for plane in prof.planes:
        if plane.name.startswith(DEVICE_PLANE):
            chip = plane.name[len(DEVICE_PLANE):]
            if not chip.isdigit():
                continue
            for line in plane.lines:
                if line.name != OP_LINE:
                    continue
                chips.add(chip)
                for e in line.events:
                    name = e.name.split(" = ", 1)[0].lstrip("%")
                    ops.append([int(chip), name, int(e.start_ns),
                                int(e.end_ns)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIXES):
                        spans.append([e.name, int(e.start_ns),
                                      int(e.end_ns)])
    win = [s for s in spans if s[0] == WINDOW_SPAN]
    if len(win) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN!r} span in the "
                           f"trace, found {len(win)}")
    return {"device_ops": ops, "spans": spans,
            "window": [win[0][1], win[0][2]], "chips": max(len(chips), 1)}


def base_name(name: str) -> str:
    """An op's name without its instance number: ``copy.109`` -> copy."""
    return re.sub(r"\.\d+$", "", name)


# ---------------- interval arithmetic ----------------

def union(intervals) -> list[tuple[int, int]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: list[list[int]] = []
    for s, e in sorted((int(s), int(e)) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals) -> int:
    return sum(e - s for s, e in intervals)


def intersect(a, b) -> list[tuple[int, int]]:
    """Intersection of two disjoint sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def gaps(busy, lo: int, hi: int) -> list[tuple[int, int]]:
    """The idle intervals of [lo, hi) between disjoint sorted ``busy``."""
    out, cur = [], lo
    for s, e in clip(busy, lo, hi):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


# ---------------- the numbers ----------------

def leaves(rec: dict) -> list:
    """The ops that hold no other op (a ``while`` holds its body's), so
    that summed op times count each instant once."""
    out = []
    per = defaultdict(list)
    for op in rec["device_ops"]:
        per[op[0]].append(op)
    for ops in per.values():
        ops = sorted(ops, key=lambda o: (o[2], -o[3]))
        for i, op in enumerate(ops):
            nxt = ops[i + 1] if i + 1 < len(ops) else None
            if nxt is None or nxt[2] >= op[3] or nxt[3] > op[3]:
                out.append(op)
    return out


def busy_by_chip(rec: dict) -> dict[int, list[tuple[int, int]]]:
    """Per chip, the union of its op intervals inside the window."""
    lo, hi = rec["window"]
    per = defaultdict(list)
    for chip, _, s, e in rec["device_ops"]:
        per[chip].append((s, e))
    return {c: clip(union(v), lo, hi) for c, v in per.items()}


def busy_s(rec: dict) -> float:
    """Seconds in which an op ran on the device, averaged over chips."""
    per = busy_by_chip(rec)
    return sum(total(v) for v in per.values()) / max(rec["chips"], 1) / 1e9


def window_s(rec: dict) -> float:
    lo, hi = rec["window"]
    return (hi - lo) / 1e9


def busy_within(rec: dict, span: str) -> tuple[float, float]:
    """(device-busy seconds inside spans named ``span``, their seconds),
    averaged over chips."""
    lo, hi = rec["window"]
    inside = clip(union((s, e) for n, s, e in rec["spans"] if n == span),
                  lo, hi)
    per = busy_by_chip(rec)
    busy = sum(total(intersect(v, inside)) for v in per.values())
    return busy / max(rec["chips"], 1) / 1e9, total(inside) / 1e9


def _labeller(rec: dict):
    """label(t): the benchmark span holding instant ``t``.  The loop's
    spans run one after another on one thread, so they do not overlap."""
    sp = sorted((a, b, n) for n, a, b in rec["spans"] if n != WINDOW_SPAN)
    starts = [a for a, _, _ in sp]

    def label(t: int) -> str:
        i = bisect.bisect_right(starts, t) - 1
        return sp[i][2] if i >= 0 and t < sp[i][1] else "outside_spans"
    return label


def idle_by_label(rec: dict) -> list[list]:
    """Idle seconds of the first chip inside the window, summed by the
    span the host was in (a gap that spans several is split at their
    edges), longest first."""
    lo, hi = rec["window"]
    busy = busy_by_chip(rec)
    chip = min(busy) if busy else 0
    label = _labeller(rec)
    edges = sorted({t for n, a, b in rec["spans"] if n != WINDOW_SPAN
                    for t in (a, b)})
    out = defaultdict(int)
    for s, e in gaps(busy.get(chip, []), lo, hi):
        i, j = bisect.bisect_right(edges, s), bisect.bisect_left(edges, e)
        cuts = [s] + edges[i:j] + [e]
        for a, b in zip(cuts, cuts[1:]):
            out[label((a + b) // 2)] += b - a
    return sorted(([k, v / 1e9] for k, v in out.items()),
                  key=lambda kv: -kv[1])


def _in_window(rec: dict, ops):
    lo, hi = rec["window"]
    for _, name, s, e in ops:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield name, e - s


def op_seconds(rec: dict) -> list[list]:
    """Device seconds per op (instance numbers dropped, only ops that
    hold no other) inside the window, averaged over chips, largest
    first."""
    out = defaultdict(int)
    for name, t in _in_window(rec, leaves(rec)):
        out[base_name(name)] += t
    n = max(rec["chips"], 1)
    return sorted(([k, v / n / 1e9] for k, v in out.items()),
                  key=lambda kv: -kv[1])


def kernel_seconds(rec: dict, kernel: str) -> tuple[float, int]:
    """(device seconds, calls) of the ops named ``kernel`` inside the
    window, averaged over chips."""
    t = n = 0
    for name, dt in _in_window(rec, rec["device_ops"]):
        if base_name(name) == kernel:
            t, n = t + dt, n + 1
    c = max(rec["chips"], 1)
    return t / c / 1e9, n // c


def breakdown(rec: dict, top: int = 10) -> dict:
    return {"device_ops": op_seconds(rec)[:top],
            "idle_gaps": idle_by_label(rec)[:top]}
