"""Kernel dispatch registry — every implementation string resolves here.

One place maps config strings to callables for the three datapath
consumers, so model code never switches on strings itself:

  softmax    'float' | 'dualmode' | 'dualmode_snap'   (attention probs)
  attention  'auto' | 'naive' | 'flash' | 'flash_pallas'
             | 'flash_pallas_int' | 'flash_pallas_int3'
             | 'flash_ring' | 'flash_decode'
  activation 'gelu_exact' | ... (delegates to repro.core.activations)
  ffn        'auto' | 'dense' | 'fused_pallas'  (gated-MLP execution)

Providers register themselves at import time (``models/attention.py``
registers 'naive', ``models/flash.py`` registers 'flash' and the 'auto'
rule, ``kernels/flash_attention.py`` registers 'flash_pallas',
``kernels/flash_attention_int.py`` registers 'flash_pallas_int' (the
one-sweep snapped-max unit) and 'flash_pallas_int3' (the three-sweep
pinned oracle), ``kernels/ring_attention.py`` registers 'flash_ring',
``kernels/fused_ffn.py`` registers 'fused_pallas') — the registry itself
imports nothing from ``models``, which keeps the layering acyclic:
datapath -> kernels -> dispatch -> models.

Attention resolution is softmax-aware: ``softmax_impl='dualmode'`` (or
'dualmode_snap') can never be silently dropped.  Every registration
DECLARES its capabilities (:class:`AttentionInfo`: honored softmax
modes, differentiability, s_q=1-only, mesh needs/safety) and resolution
is driven by those declarations — the table below is GENERATED from the
live registry by ``python -m repro.analysis.audit --write-docs`` and
re-derived on every audit run; a mismatch between this text and the
registry is a CI failure (the dispatch-table pass), so regenerate
instead of hand-editing.

[dispatch-table:begin]
Explicit `attn_impl` x `softmax_impl` — identical across phases
and meshes (the ring upgrade exists only inside 'auto').
'raise' cells are intentional ValueErrors: a dual-mode word
contract is never silently dropped.

| attn_impl | float | dualmode | dualmode_snap | grad | constraints |
|---|---|---|---|---|---|
| flash | ok | raise | raise | yes | - |
| flash_decode | ok | ok | ok | no | s_q=1 only |
| flash_pallas | ok | raise | raise | yes | - |
| flash_pallas_int | raise | ok | ok | no | - |
| flash_pallas_int3 | raise | ok | raise | no | - |
| flash_ring | ok | ok | ok | yes | needs mesh, mesh-safe |
| naive | ok | ok | ok | yes | mesh-safe |

`attn_impl='auto'` by (phase, mesh), resolved on the cpu/
interpret backend — on TPU the blocked float pick is
'flash_pallas' (``models.flash.blocked_impl``); everything else
is backend-independent.

| phase | mesh | float | dualmode | dualmode_snap |
|---|---|---|---|---|
| enc (128x128) | none | naive | naive | naive |
| enc (128x128) | ring8 | naive | naive | naive |
| prefill (4096x4096) | none | flash | flash_pallas_int | flash_pallas_int |
| prefill (4096x4096) | ring8 | flash_ring | flash_ring | flash_ring |
| decode (1x65536) | none | flash_decode | flash_decode | flash_decode |
| decode (1x65536) | ring8 | naive | naive | naive |

`norm_impl` providers — a fused provider must carry ALL three
block seams (``dispatch.NORM_SEAMS``); 'unfused' rows run the
reference norms in models/layers.py.  'auto' resolves to
'fused_pallas' on TPU and 'dense' elsewhere, for `norm_impl`
and `ffn_impl` alike (dispatch.resolve_norm / resolve_ffn).

| norm_impl | residual_norm | norm_linear | norm_glu |
|---|---|---|---|
| dense | unfused | unfused | unfused |
| fused_pallas | ok | ok | ok |
[dispatch-table:end]

Resolution is also shape- and backend-aware through the 'auto' rule
(registered by ``models/flash.py``): s_q=1 against a long KV cache picks
the split-KV decode kernel 'flash_decode' (in BOTH softmax modes — the
snapped monoid made the split fold word-exact); wide-q blocked shapes
pick the compiled Pallas kernel on TPU and the pure-JAX blocked path on
interpret backends (where interpret-mode Pallas loses to XLA).

Resolution is also mesh-aware when the caller opts in with a
``ring_axis``: when 'auto' lands on a blocked impl (float OR int) AND
the ambient ``jax.set_mesh`` context shards the KV sequence over that
axis (both sequence dims divisible), the pick upgrades to 'flash_ring'
— the sequence-parallel ring composition of the same kernel, which
folds float (m, l, acc) or snapped int (m, S, acc) hop partials
according to ``softmax_impl``.
"""
from __future__ import annotations

from contextlib import contextmanager
from dataclasses import dataclass
from typing import Callable

import jax

from repro.core import softmax_unit as _unit
from repro.core.activations import get_activation  # noqa: F401  (re-export)

# --------------------------------------------------------------------------
# softmax (attention probabilities)
# --------------------------------------------------------------------------

_SOFTMAX: dict[str, Callable] = {}


def register_softmax(name: str, fn: Callable) -> None:
    _SOFTMAX[name] = fn


def get_softmax(impl: str) -> Callable:
    """Attention-softmax implementation switch.

    'float'         : jax.nn.softmax (fp32 accumulate)
    'dualmode'      : the paper's unit, bit-accurate int path (jnp
                      emulation — same numerics the three-sweep Pallas
                      kernel executes)
    'dualmode_snap' : the snapped-max variant of the unit — the
                      whole-row oracle of every STREAMED dual-mode path
                      (one-sweep int flash, dual-mode decode/ring)
    """
    try:
        return _SOFTMAX[impl]
    except KeyError:
        raise ValueError(
            f"unknown softmax impl {impl!r}; have {sorted(_SOFTMAX)}")


register_softmax("float", lambda x: jax.nn.softmax(x, axis=-1))
register_softmax(
    "dualmode",
    lambda x: _unit.softmax_dualmode(
        x.astype("float32"), axis=-1).astype(x.dtype))
register_softmax(
    "dualmode_snap",
    lambda x: _unit.softmax_dualmode_snap(
        x.astype("float32"), axis=-1).astype(x.dtype))


# --------------------------------------------------------------------------
# attention (scores -> probs -> combine execution strategy)
# --------------------------------------------------------------------------

_ATTENTION: dict[str, Callable] = {}
_ATTENTION_AUTO: list[Callable] = []   # single slot: (s_q, t) -> impl name


@dataclass(frozen=True)
class AttentionInfo:
    """Declared capabilities of one registered attention impl.

    Resolution, the static auditor (``repro.analysis``), and the
    generated resolution table are all driven by these declarations, so
    an entry whose behavior drifts from its metadata fails the audit's
    dispatch-table pass.

    modes       softmax_impl values the entry honors.  Float-datapath
                kernels declare {'float'}; the int kernels declare the
                word contracts they stream ('dualmode_snap' for snapped
                words); dual-mode-CAPABLE entries declare all three and
                route internally.
    grad        differentiable (JAX AD or a custom VJP).  The int word
                paths are forward-only: step-quantized words have zero
                gradient a.e.
    decode_only entry contract is s_q == 1 rows (split-KV decode).
    needs_mesh  entry requires an ambient mesh carrying ``ring_axis``.
    mesh_safe   lowering against a KV-sequence-sharded cache does NOT
                materialize the full cache per chip (the whole-cache
                all-gather the analysis mesh-safety pass detects).
    note        one-line annotation for the generated table.
    """
    modes: frozenset[str]
    grad: bool
    decode_only: bool = False
    needs_mesh: bool = False
    mesh_safe: bool = False
    note: str = ""


_ATTENTION_INFO: dict[str, AttentionInfo] = {}

# analysis-only ambient-mesh override (see analysis_mesh below)
_MESH_OVERRIDE: list = []


def ambient_mesh():
    """The active ``with jax.set_mesh(mesh):`` context's Mesh, or None.

    The ring-attention provider and the 'auto' ring upgrade read the
    mesh from here, so model code threads only the ``ring_axis`` string
    (configs stay pure data) and the same resolution works at trace
    time inside jit."""
    if _MESH_OVERRIDE:
        return _MESH_OVERRIDE[-1]
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def ring_axis_size(ring_axis: str | None) -> int:
    """Size of ``ring_axis`` on the ambient mesh (0 when absent/unset)."""
    if not ring_axis:
        return 0
    mesh = ambient_mesh()
    if mesh is None or ring_axis not in mesh.axis_names:
        return 0
    return mesh.shape[ring_axis]


class _AnalysisMesh:
    """Resolution-level stand-in for a Mesh — only the attributes the
    resolver reads (``axis_names``, ``shape``, ``empty``) exist, so the
    dispatch matrix can be enumerated without emulated devices."""

    def __init__(self, axis_sizes: dict[str, int]):
        self.shape = dict(axis_sizes)
        self.axis_names = tuple(axis_sizes)
        self.empty = not axis_sizes

    def __repr__(self) -> str:   # pragma: no cover - debug aid
        return f"_AnalysisMesh({self.shape})"


@contextmanager
def analysis_mesh(axis_sizes: dict[str, int]):
    """Make :func:`ambient_mesh` report a mesh with ``axis_sizes``.

    ANALYSIS-ONLY seam: ``repro.analysis.dispatch_table`` enumerates the
    (impl x softmax x phase x mesh) resolution matrix under meshes that
    need not exist on the current backend.  Never use this to RUN a
    computation — only :func:`resolve_attention` and the 'auto' rule
    consult :func:`ambient_mesh`, and only they see the stand-in.
    """
    _MESH_OVERRIDE.append(_AnalysisMesh(axis_sizes))
    try:
        yield
    finally:
        _MESH_OVERRIDE.pop()


def register_attention(name: str, fn: Callable, *,
                       modes, grad: bool, decode_only: bool = False,
                       needs_mesh: bool = False, mesh_safe: bool = False,
                       note: str = "") -> None:
    """fn(q, k, v, *, q_pos, kv_valid, causal, scale, softmax_impl,
    ring_axis) -> (B,S,K,G,hv), plus the declared capability metadata
    (see :class:`AttentionInfo`).

    Every implementation takes the full contract (``ring_axis`` names
    the mesh axis the sequence-parallel ring rotates over; only
    'flash_ring' acts on it, the others accept and ignore it).  The
    ``modes`` declaration is load-bearing: resolution refuses any
    (impl, softmax_impl) pair outside it, and the entry itself must
    raise on undeclared modes — ``repro.analysis`` audits both sides,
    and an impl present in the registry WITHOUT metadata (registered by
    poking ``_ATTENTION`` directly) is an audit failure."""
    _ATTENTION[name] = fn
    _ATTENTION_INFO[name] = AttentionInfo(
        modes=frozenset(modes), grad=grad, decode_only=decode_only,
        needs_mesh=needs_mesh, mesh_safe=mesh_safe, note=note)


def attention_info(name: str) -> AttentionInfo:
    """Declared capabilities of ``name`` (loads providers on demand)."""
    if name not in _ATTENTION_INFO:
        _load_attention_providers()
    try:
        return _ATTENTION_INFO[name]
    except KeyError:
        raise ValueError(f"unknown attention impl {name!r}; "
                         f"have {sorted(_ATTENTION)}")


def attention_impls() -> list[str]:
    """All registered attention impl names (providers loaded)."""
    _load_attention_providers()
    return sorted(_ATTENTION)


def set_attention_auto_rule(rule: Callable) -> None:
    """rule(s_q, t_kv) -> implementation name, used for impl='auto'."""
    _ATTENTION_AUTO[:] = [rule]


def _load_attention_providers() -> None:
    """Import the provider modules so their registrations run — callers
    that resolve through the registry directly (serve engine, notebooks)
    must not depend on having imported ``repro.models`` first."""
    import repro.kernels.flash_attention      # noqa: F401
    import repro.kernels.flash_attention_int  # noqa: F401
    import repro.kernels.flash_decode         # noqa: F401
    import repro.kernels.ring_attention       # noqa: F401
    import repro.models.attention             # noqa: F401  (naive+flash+rule)


def resolve_attention(impl: str, s_q: int, t_kv: int,
                      softmax_impl: str = "float",
                      ring_axis: str | None = None) -> str:
    """Resolve 'auto' to a concrete implementation name.

    Softmax-aware and METADATA-DRIVEN: every impl's registration
    declares the softmax modes it honors (:class:`AttentionInfo`), and
    'dualmode'/'dualmode_snap' are numerics contracts, so resolution
    guarantees the bit-accurate unit actually executes —

      * 'auto' + a dual-mode contract: short rows stay 'naive'
        (whole-row unit); shapes the auto rule would stream through a
        float-only blocked path go to 'flash_pallas_int' (the unit's
        one-sweep snapped-max kernel) instead; s_q=1 decode rows keep
        'flash_decode' — its entry runs the snapped int split path, so
        long-cache dual-mode decode gets the same split-KV parallelism
        as float; the ring opt-in (below) upgrades to 'flash_ring',
        whose entry folds snapped int hop partials.
      * any explicit impl + a softmax mode outside its declared
        ``modes``: ValueError — e.g. 'flash'/'flash_pallas' (float
        log-domain by construction) with 'dualmode', or
        'flash_pallas_int'/'flash_pallas_int3' (the kernels ARE the
        unit) with 'float'.  Silently dropping a word contract is
        exactly the bug this guard exists to prevent.

    Mesh-aware (opt-in): with a non-empty ``ring_axis``, an 'auto' pick
    of a blocked path — float OR int — upgrades to 'flash_ring' when the
    ambient ``jax.set_mesh`` context carries that axis with size > 1 and
    both sequence dims divide it — the shapes where the KV sequence
    actually shards.  Configs opt in via ``ModelConfig.ring_axis``; the
    default (``""``) never changes today's resolution.
    """
    if softmax_impl not in _SOFTMAX:
        raise ValueError(f"unknown softmax impl {softmax_impl!r}; "
                         f"have {sorted(_SOFTMAX)}")
    if impl == "auto" and not _ATTENTION_AUTO:
        _load_attention_providers()
    if impl == "auto":
        impl = _ATTENTION_AUTO[0](s_q, t_kv) if _ATTENTION_AUTO else "naive"
        if softmax_impl not in attention_info(impl).modes:
            # the auto rule picked a float-only blocked path under a
            # dual-mode word contract: the one-sweep snapped-max unit
            # kernel streams the same shapes bit-accurately
            impl = "flash_pallas_int"
        if impl in ("flash", "flash_pallas", "flash_pallas_int"):
            n = ring_axis_size(ring_axis)
            if n > 1 and s_q % n == 0 and t_kv % n == 0:
                # the ring entry folds float (m, l, acc) or snapped int
                # (m, S, acc) hop partials according to softmax_impl
                impl = "flash_ring"
    else:
        info = attention_info(impl)        # raises on unknown impls
        if softmax_impl not in info.modes:
            raise ValueError(
                f"attn_impl={impl!r} declares softmax modes "
                f"{sorted(info.modes)} and cannot honor "
                f"softmax_impl={softmax_impl!r} — the dualmode word "
                "contract is never silently dropped; use attn_impl="
                "'auto' (routes to 'naive'/'flash_pallas_int'/"
                "'flash_decode'), or an impl declaring the mode")
    if impl not in _ATTENTION:
        _load_attention_providers()
    if impl not in _ATTENTION:
        raise ValueError(
            f"unknown attention impl {impl!r}; have {sorted(_ATTENTION)}")
    return impl


def get_attention(impl: str) -> Callable:
    if impl not in _ATTENTION:
        _load_attention_providers()
    return _ATTENTION[impl]


# --------------------------------------------------------------------------
# paged attention (block-table KV gather variants)
# --------------------------------------------------------------------------

# Parallel registry for implementations that read K/V through a block
# pool + per-request block table instead of contiguous (B, T, ...) rows.
# Keyed by the SAME names as _ATTENTION: resolution stays the dense
# resolve_attention above (paged changes the memory layout, not the
# numerics contract), and the model layer asks get_paged_attention for
# the resolved name — falling back to a dense gather when the impl has
# no native block-table mode.

_PAGED_ATTENTION: dict[str, Callable] = {}


def register_paged_attention(name: str, fn: Callable) -> None:
    """fn(q, k_pool, v_pool, *, block_tables, q_pos, kv_valid, causal,
    scale, softmax_impl, ring_axis) -> (B,1,K,G,hv).

    ``k_pool``/``v_pool`` are (N_blocks, block_size, K, h) pools;
    ``block_tables`` is a (B, max_blocks) int32 map from each row's
    logical block index to its pool block (sentinel block 0 for entries
    past the row's length).  Everything after the layout — masking,
    causality, the partial-merge fold — matches the dense contract."""
    _PAGED_ATTENTION[name] = fn


def get_paged_attention(name: str) -> Callable | None:
    """The block-table native variant of ``name``, or None when the impl
    only speaks contiguous rows (caller gathers dense and dispatches)."""
    if name not in _PAGED_ATTENTION:
        _load_attention_providers()
    return _PAGED_ATTENTION.get(name)


# --------------------------------------------------------------------------
# FFN (gated-MLP execution strategy)
# --------------------------------------------------------------------------

_FFN: dict[str, Callable | None] = {"dense": None}


def register_ffn(name: str, fn: Callable) -> None:
    """fn(x2d, wg, wu, mode) -> (M, F) fused gate-matmul + activation."""
    _FFN[name] = fn


def resolve_ffn(impl: str) -> str:
    """Resolve ``ffn_impl='auto'`` to a concrete execution strategy.

    'auto' picks 'fused_pallas' on TPU — the compiled fused gated-matmul
    + activation epilogue — and 'dense' everywhere else, where
    interpret-mode Pallas loses to the plain XLA graph.  Explicit strings
    ('dense', 'fused_pallas') pass through untouched, so a config that
    pins an impl keeps it on every backend.
    """
    if impl == "auto":
        return "fused_pallas" if jax.default_backend() == "tpu" else "dense"
    return impl


def get_ffn(impl: str) -> Callable | None:
    """None means the plain (unfused) path; otherwise a fused GLU kernel."""
    if impl not in _FFN and impl == "fused_pallas":
        import repro.kernels.fused_ffn  # noqa: F401  (self-registers)
    try:
        return _FFN[impl]
    except KeyError:
        raise ValueError(f"unknown ffn impl {impl!r}; have {sorted(_FFN)}")


# --------------------------------------------------------------------------
# Norm (fused norm-seam execution strategy)
# --------------------------------------------------------------------------
#
# A norm provider is a dict of the block's three fusable seams —
#   'residual_norm' (x, r, g, b, kind, eps)  -> (x + r, norm(x + r))
#   'norm_linear'   (x, g, b, w, kind, eps)  -> norm(x) @ w
#   'norm_glu'      (x, g, b, wg, wu, kind, eps, mode) -> act(h@wg)*(h@wu)
# — registered as one unit so the dispatch-table auditor can check the
# provider contract (all three seams present and callable).

NORM_SEAMS = ("residual_norm", "norm_linear", "norm_glu")

_NORM: dict[str, dict[str, Callable] | None] = {"dense": None}


def register_norm(name: str, seams: dict[str, Callable]) -> None:
    """Register a fused-norm provider: a dict keyed by NORM_SEAMS."""
    _NORM[name] = seams


def resolve_norm(impl: str) -> str:
    """Resolve ``norm_impl='auto'`` — same policy as :func:`resolve_ffn`:
    'fused_pallas' on TPU, 'dense' elsewhere; explicit strings pass
    through untouched."""
    if impl == "auto":
        return "fused_pallas" if jax.default_backend() == "tpu" else "dense"
    return impl


def get_norm(impl: str) -> dict[str, Callable] | None:
    """None means the plain (unfused) norms; otherwise the seam dict."""
    if impl not in _NORM and impl == "fused_pallas":
        import repro.kernels.fused_norm  # noqa: F401  (self-registers)
    try:
        return _NORM[impl]
    except KeyError:
        raise ValueError(f"unknown norm impl {impl!r}; have {sorted(_NORM)}")
