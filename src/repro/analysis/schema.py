"""One declarative validator for machine-readable artifacts.

``analysis/audit.py`` validates every AUDIT.json it writes against
``AUDIT_SPEC``/``AUDIT_RULES`` through this engine.  A schema is data:

  type            isinstance check (bool is NOT an int here)
  {k: spec}       dict with at least these keys, each value checked;
                  extra keys are allowed (artifacts may grow)
  [spec]          list/tuple, every element checked
  ("keys", spec)  dict with arbitrary keys, every VALUE checked
  ("any_of", *s)  first matching alternative wins
  ("eq", v)       exact value
  ("in", vs)      membership
  callable        predicate(value) -> True, or False/str (the error)

Cross-field invariants that don't fit a tree walk ride along as
``rules``: (description, predicate(whole_obj)) pairs.

``validate`` collects EVERY error and raises one AssertionError listing
them — a CI failure names all the drifted fields at once.
"""
from __future__ import annotations

import json


def check(obj, spec, path: str = "$") -> list[str]:
    """All schema violations of ``obj`` against ``spec`` (empty = ok)."""
    if isinstance(spec, type):
        if spec in (int, float) and isinstance(obj, bool):
            return [f"{path}: expected {spec.__name__}, got bool"]
        if spec is float and isinstance(obj, int):
            return []
        if not isinstance(obj, spec):
            return [f"{path}: expected {spec.__name__}, "
                    f"got {type(obj).__name__}"]
        return []
    if isinstance(spec, tuple):
        tag = spec[0]
        if tag == "any_of":
            fails = []
            for alt in spec[1:]:
                errs = check(obj, alt, path)
                if not errs:
                    return []
                fails.extend(errs)
            return [f"{path}: no alternative matched "
                    f"({'; '.join(fails)})"]
        if tag == "eq":
            return ([] if obj == spec[1]
                    else [f"{path}: expected {spec[1]!r}, got {obj!r}"])
        if tag == "in":
            return ([] if obj in spec[1]
                    else [f"{path}: {obj!r} not in {sorted(spec[1])!r}"])
        if tag == "keys":
            if not isinstance(obj, dict):
                return [f"{path}: expected dict, got {type(obj).__name__}"]
            out = []
            for k, v in obj.items():
                out.extend(check(v, spec[1], f"{path}.{k}"))
            return out
        raise ValueError(f"unknown spec tag {tag!r} at {path}")
    if isinstance(spec, dict):
        if not isinstance(obj, dict):
            return [f"{path}: expected dict, got {type(obj).__name__}"]
        out = []
        for k, sub in spec.items():
            if k not in obj:
                out.append(f"{path}: missing key {k!r}")
            else:
                out.extend(check(obj[k], sub, f"{path}.{k}"))
        return out
    if isinstance(spec, list):
        if not isinstance(obj, (list, tuple)):
            return [f"{path}: expected list, got {type(obj).__name__}"]
        out = []
        for i, item in enumerate(obj):
            out.extend(check(item, spec[0], f"{path}[{i}]"))
        return out
    if callable(spec):
        try:
            res = spec(obj)
        except Exception as exc:
            return [f"{path}: predicate raised {exc!r}"]
        if res is True or res is None:
            return []
        return [f"{path}: {res if isinstance(res, str) else 'predicate failed'}"]
    raise ValueError(f"unintelligible spec {spec!r} at {path}")


def validate(obj, spec, rules=(), name: str = "object") -> None:
    """Raise AssertionError listing every schema/rule violation."""
    errors = check(obj, spec, "$")
    for desc, pred in rules:
        try:
            ok = pred(obj)
        except Exception as exc:
            ok = False
            desc = f"{desc} (rule raised {exc!r})"
        if not ok:
            errors.append(f"rule failed: {desc}")
    assert not errors, f"{name} schema violations:\n  " + "\n  ".join(errors)


def validate_file(path: str, spec, rules=(), name: str | None = None):
    with open(path) as fh:
        obj = json.load(fh)
    validate(obj, spec, rules, name or path)
    return obj


# ---------------------------------------------------------------------------
# AUDIT.json (the auditor's own artifact goes through the same engine)
# ---------------------------------------------------------------------------

_STATUS = ("in", {"ok", "fail", "skipped"})
AUDIT_SPEC = {
    "generated_by": str,
    "strict": bool,
    "ok": bool,
    "passes": {
        "int_purity": {"status": _STATUS, "checked": [str],
                       "violations": [{"path": str, "prim": str,
                                       "where": str}]},
        "vmem": {"status": _STATUS, "over_budget": int,
                 "trace_mismatches": [str],
                 "cells": [{"kernel": str, "call": str, "cell": str,
                            "bytes": int, "budget": int, "ok": bool}]},
        "mesh_safety": {"status": _STATUS,
                        "impls": [{"impl": str, "ok": bool,
                                   "declared_mesh_safe": bool,
                                   "whole_cache_gather": bool,
                                   "largest_gather_bytes": int,
                                   "full_kv_bytes": int}]},
        "dispatch_table": {"status": _STATUS, "cells": int,
                           "problems": [str], "drift": [str]},
    },
}
# coverage floors apply only to passes CLAIMING "ok" — a failing pass
# (e.g. a seeded --fixture run over one subject) already did its job
AUDIT_RULES = [
    ("ok iff no pass failed",
     lambda a: a["ok"] == all(p["status"] != "fail"
                              for p in a["passes"].values())),
    ("an ok purity pass walked at least the unit + kernel paths",
     lambda a: a["passes"]["int_purity"]["status"] != "ok"
     or len(a["passes"]["int_purity"]["checked"]) >= 6),
    ("an ok vmem pass priced the whole grid",
     lambda a: a["passes"]["vmem"]["status"] != "ok"
     or len(a["passes"]["vmem"]["cells"]) >= 10),
    ("an ok dispatch pass enumerated the full matrix",
     lambda a: a["passes"]["dispatch_table"]["status"] != "ok"
     or a["passes"]["dispatch_table"]["cells"] >= 100),
]

