"""The auditor audits itself: each static pass is exercised on the real
tree (must be clean) AND on a seeded violation (must be caught).  An
analysis subsystem whose failure modes are untested is just decoration —
these tests are what keeps the four passes honest."""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

from repro.analysis import dispatch_table, int_purity, schema, vmem
from repro.kernels import dispatch, tiling

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# schema: the one declarative validator
# ---------------------------------------------------------------------------


def test_schema_type_and_eq_and_in():
    assert schema.check(3, int) == []
    assert schema.check(True, int)          # bool is not an int here
    assert schema.check(3, float) == []     # ints pass float slots
    assert schema.check("x", ("eq", "x")) == []
    assert schema.check("y", ("eq", "x"))
    assert schema.check("a", ("in", {"a", "b"})) == []
    assert schema.check("c", ("in", {"a", "b"}))


def test_schema_containers_and_any_of():
    spec = {"rows": [{"n": int}], "tag": ("any_of", int, str)}
    assert schema.check({"rows": [{"n": 1}], "tag": "t"}, spec) == []
    errs = schema.check({"rows": [{"n": "bad"}], "tag": 1.5}, spec)
    assert len(errs) == 2                   # both collected, not fail-fast
    assert any("$.rows[0].n" in e for e in errs)
    assert schema.check({"a": 1, "b": 2}, ("keys", int)) == []
    assert schema.check({"a": "x"}, ("keys", int))


def test_schema_validate_raises_with_all_errors():
    with pytest.raises(AssertionError) as ei:
        schema.validate({"a": "x"}, {"a": int, "b": int},
                        [("always fails", lambda d: False)], "thing")
    msg = str(ei.value)
    assert "$.a" in msg and "missing key 'b'" in msg and "always fails" in msg


# ---------------------------------------------------------------------------
# cold registry: a fresh interpreter that imports only the dispatch module
# must still find every provider (they self-register on first lookup)
# ---------------------------------------------------------------------------

_COLD_PROBES = {
    "attention": """
import numpy as np, jax.numpy as jnp
from repro.kernels import dispatch
assert "repro.kernels.flash_attention_int" not in sys.modules
assert dispatch.resolve_attention(
    "auto", 4096, 4096, softmax_impl="dualmode") == "flash_pallas_int"
rng = np.random.default_rng(0)
q = jnp.asarray(rng.normal(size=(1, 8, 1, 1, 8)), jnp.float32)
k = jnp.asarray(rng.normal(size=(1, 8, 1, 8)), jnp.float32)
v = jnp.asarray(rng.normal(size=(1, 8, 1, 8)), jnp.float32)
out = dispatch.get_attention("flash_pallas_int")(
    q, k, v, q_pos=jnp.arange(8)[None], kv_valid=jnp.ones((1, 8), bool),
    causal=True, scale=None, softmax_impl="dualmode")
assert out.shape == (1, 8, 1, 1, 8) and bool(jnp.isfinite(out).all())
""",
    "ffn": """
import numpy as np, jax.numpy as jnp
from repro.kernels import dispatch
from repro.kernels import datapath as dp
assert "repro.kernels.fused_ffn" not in sys.modules
fn = dispatch.get_ffn("fused_pallas")
rng = np.random.default_rng(0)
x = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)
wg = jnp.asarray(rng.normal(size=(32, 64)) / 6, jnp.float32)
wu = jnp.asarray(rng.normal(size=(32, 64)) / 6, jnp.float32)
y = fn(x, wg, wu, "silu")
want = dp.pair_act(x @ wg, "silu") * (x @ wu)
assert float(jnp.abs(y - want).max()) <= 5e-5
""",
    "norm": """
import numpy as np, jax.numpy as jnp
from repro.kernels import dispatch
from repro.kernels import datapath as dp
assert "repro.kernels.fused_norm" not in sys.modules
assert dispatch.resolve_norm("auto") == "dense"        # no TPU here
prov = dispatch.get_norm("fused_pallas")
assert sorted(prov) == sorted(dispatch.NORM_SEAMS)
rng = np.random.default_rng(0)
x = jnp.asarray(rng.normal(size=(16, 64)), jnp.float32)
r = jnp.asarray(rng.normal(size=(16, 64)), jnp.float32)
g = jnp.asarray(1 + 0.1 * rng.normal(size=(64,)), jnp.float32)
w = jnp.asarray(rng.normal(size=(64, 96)) / 8, jnp.float32)
xn, h = prov["residual_norm"](x, r, g, None, kind="rms", eps=1e-6)
assert bool(jnp.array_equal(xn, x + r))
assert float(jnp.abs(h - dp.rmsnorm(x + r, g, 1e-6)).max()) <= 1e-5
y = prov["norm_linear"](x, g, None, w, kind="rms", eps=1e-6)
assert float(jnp.abs(y - dp.rmsnorm(x, g, 1e-6) @ w).max()) <= 5e-5
z = prov["norm_glu"](x, g, None, w, w, kind="rms", eps=1e-6, mode="gelu")
hd = dp.rmsnorm(x, g, 1e-6)
assert float(jnp.abs(z - dp.pair_act(hd @ w, "gelu") * (hd @ w)).max()) \
    <= 5e-5
""",
}


@pytest.mark.parametrize("kind", sorted(_COLD_PROBES))
def test_cold_interpreter_finds_provider(kind):
    """No pytest, conftest or model import has registered anything: the
    lookup itself must import the provider module and run its kernel."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"
    code = "import sys\n" + _COLD_PROBES[kind] + "print('COLD_OK')\n"
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300,
                       cwd=REPO)
    assert r.returncode == 0 and "COLD_OK" in r.stdout, \
        f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"


# ---------------------------------------------------------------------------
# int-purity: clean tree, caught fixture, no false positive on the
# finishing divide
# ---------------------------------------------------------------------------


def test_int_purity_real_paths_clean():
    out = int_purity.run()
    assert out["status"] == "ok", out["violations"]
    # the walk must actually cover the unit, the pallas softmax tile and
    # every registered int attention entry — an empty 'checked' list
    # passing would mean the pass silently audits nothing
    checked = set(out["checked"])
    assert {"softmax:dualmode", "softmax:dualmode_snap", "gelu:dualmode",
            "softmax_pallas:int", "rmsnorm:dualmode",
            "layernorm:dualmode"} <= checked
    assert any(c.startswith("attn:flash_pallas_int:") for c in checked)
    assert any(c.startswith("attn:flash_decode:") for c in checked)


def test_int_purity_catches_exp_on_the_word_lattice():
    def bad(x):
        words = (x * 127.0).astype(jnp.int32)
        e = jnp.exp(words.astype(jnp.float32) * (1.0 / 127.0))
        return (e * 127.0).astype(jnp.int32)

    v = int_purity.audit_fn(bad, (jnp.zeros((8, 128), jnp.float32),),
                            "fixture")
    assert [x.prim for x in v] == ["exp"]


def test_int_purity_allows_float_div_after_the_words():
    """The blocked kernels' finishing acc/l divide never feeds an int var
    — the exact reason the rule is int->op->int, not 'no div anywhere'."""
    def fine(x):
        words = (x * 127.0).astype(jnp.int32)
        probs = words.astype(jnp.float32)
        return probs / (probs.sum(-1, keepdims=True) + 1.0)

    assert int_purity.audit_fn(
        fine, (jnp.zeros((8, 128), jnp.float32),), "p") == []


# ---------------------------------------------------------------------------
# vmem: every grid cell within budget, oversubscribed plan caught,
# declarations honest vs traced kernels
# ---------------------------------------------------------------------------


def test_vmem_grid_within_budget():
    out = vmem.run()
    assert out["status"] == "ok", out
    assert out["over_budget"] == 0
    assert len(out["cells"]) >= 10          # the whole grid, not a sample
    kernels = {c["kernel"] for c in out["cells"]}
    assert {"flash_attention", "flash_attention_int", "flash_decode",
            "fused_ffn", "fused_norm"} <= kernels
    # all three norm seams priced, not just one
    norm_calls = {c["call"] for c in out["cells"]
                  if c["kernel"] == "fused_norm"}
    assert {"resnorm_fwd", "norm_linear_fwd", "norm_glu_fwd"} <= norm_calls


def test_vmem_catches_oversubscribed_plan():
    plan = {"in:x": ((4096, 4096), "float32")}
    assert vmem.plan_footprint(plan) > tiling.VMEM_CORE_BUDGET


def test_vmem_footprint_arithmetic():
    plan = {"in:a": ((8, 128), "float32"), "out:b": ((8, 128), "float32"),
            "scratch:s": ((8, 128), "int32")}
    # 2 x (4096 + 4096) io + 4096 scratch
    assert vmem.plan_footprint(plan) == 2 * 2 * 8 * 128 * 4 + 8 * 128 * 4


def test_vmem_cross_check_declared_vs_traced():
    assert vmem.cross_check() == []


# ---------------------------------------------------------------------------
# dispatch-table truth
# ---------------------------------------------------------------------------


def test_dispatch_matrix_consistent():
    m = dispatch_table.enumerate_matrix()
    assert m["problems"] == []
    assert m["cells"] >= 100


def test_dispatch_matrix_pins_the_published_routing():
    m = dispatch_table.enumerate_matrix()
    auto = m["auto"]
    # the cells ARCHITECTURE.md promises
    assert auto[("prefill", "none", "dualmode")] == "flash_pallas_int"
    assert auto[("prefill", "ring8", "float")] == "flash_ring"
    assert auto[("decode", "none", "dualmode")] == "flash_decode"
    # the mesh gate: sharded decode stays on the shardable naive graph
    assert auto[("decode", "ring8", "dualmode")] == "naive"
    assert auto[("enc", "none", "float")] == "naive"
    # explicit float impls refuse the word contract
    assert m["explicit"]["flash"]["dualmode"] == "raise"
    assert m["explicit"]["flash_pallas"]["dualmode_snap"] == "raise"
    assert m["explicit"]["flash_pallas_int"]["float"] == "raise"


def test_dispatch_docs_not_drifted():
    """The tables committed in dispatch.py and ARCHITECTURE.md must match
    a fresh enumeration — regenerate with --write-docs, never by hand."""
    assert dispatch_table.check_docs() == []


def test_dispatch_catches_rogue_registry_entry():
    dispatch._load_attention_providers()
    dispatch._ATTENTION["rogue"] = lambda *a, **k: None
    try:
        m = dispatch_table.enumerate_matrix()
    finally:
        dispatch._ATTENTION.pop("rogue", None)
    assert any("rogue" in p and "without AttentionInfo" in p
               for p in m["problems"])


def test_dispatch_catches_half_fused_norm_provider():
    """A norm provider missing a NORM_SEAMS callable is exactly the
    half-fused block the provider contract refuses."""
    dispatch.get_norm("fused_pallas")
    dispatch._NORM["rogue"] = {"residual_norm": lambda *a, **k: None}
    try:
        m = dispatch_table.enumerate_matrix()
    finally:
        dispatch._NORM.pop("rogue", None)
    missing = [p for p in m["problems"]
               if "rogue" in p and "missing seam" in p]
    assert len(missing) == 2, m["problems"]       # norm_linear + norm_glu


# ---------------------------------------------------------------------------
# the CLI end to end (subprocess: the mesh pass needs XLA_FLAGS set
# before jax import, which an in-process test can't do)
# ---------------------------------------------------------------------------


def _run_audit(tmp_path, *args, timeout=560):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)              # the CLI sets its own
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = os.path.join(str(tmp_path), "AUDIT.json")
    r = subprocess.run(
        [sys.executable, "-m", "repro.analysis.audit", "--out", out,
         *args],
        env=env, capture_output=True, text=True, timeout=timeout,
        cwd=REPO)
    return r, out


def test_audit_cli_mesh_pass_clean(tmp_path):
    r, out = _run_audit(tmp_path, "--strict", "--passes", "mesh_safety")
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    with open(out) as fh:
        audit = json.load(fh)
    schema.validate(audit, schema.AUDIT_SPEC, schema.AUDIT_RULES)
    ms = audit["passes"]["mesh_safety"]
    assert ms["status"] == "ok"
    by_impl = {r_["impl"]: r_ for r_ in ms["impls"]}
    # naive really shards; the pallas kernels really don't — and say so
    assert by_impl["naive"]["declared_mesh_safe"]
    assert not by_impl["naive"]["whole_cache_gather"]
    assert not by_impl["flash_decode"]["declared_mesh_safe"]
    assert by_impl["flash_decode"]["whole_cache_gather"]


def test_audit_cli_mesh_fixture_detected(tmp_path):
    r, _ = _run_audit(tmp_path, "--fixture", "mesh", "--passes", "")
    assert r.returncode != 0, "falsely-declared mesh_safe went undetected"
    assert "detected as intended" in r.stdout


def test_audit_cli_purity_and_dispatch_fixtures_detected(tmp_path):
    for fixture in ("int_purity", "dispatch", "vmem", "norm"):
        r, _ = _run_audit(tmp_path, "--fixture", fixture, "--passes", "")
        assert r.returncode != 0, f"fixture {fixture} went undetected"
        assert "detected as intended" in r.stdout
