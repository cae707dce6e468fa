"""Logical-axis sharding rules (MaxText-style).

Model code annotates activations with *logical* axis names; an execution
choice (core/choices.py) installs a rule set mapping logical names to mesh
axes. This is the mechanism through which Swan's execution choices rebind the
distribution strategy without touching model code.

Logical axes:
  batch   - data-parallel batch dim
  seq     - sequence (SP) dim
  fsdp    - weight dim sharded for FSDP (usually d_model / vocab rows)
  tp      - tensor-parallel dim (heads, ffn hidden, vocab cols)
  ep      - expert-parallel dim (MoE expert axis)
  kvseq   - KV-cache sequence dim (context parallel decode)
"""
from __future__ import annotations

import contextlib
import re
import threading
from typing import Optional, Sequence, Tuple, Union

import jax
from jax.sharding import PartitionSpec as P

AxisBinding = Union[None, str, Tuple[str, ...]]

# Default rule set: single-pod (data, model) mesh, FSDP+TP.
DEFAULT_RULES: dict[str, AxisBinding] = {
    "batch": ("data",),
    "seq": None,
    "fsdp": "data",
    "tp": "model",
    "ep": "model",
    "kvseq": "model",
}

_state = threading.local()


def get_rules() -> dict[str, AxisBinding]:
    return getattr(_state, "rules", DEFAULT_RULES)


@contextlib.contextmanager
def axis_rules(rules: dict[str, AxisBinding]):
    """Install a logical->mesh axis rule set for the enclosed scope."""
    prev = getattr(_state, "rules", None)
    _state.rules = dict(rules)
    try:
        yield
    finally:
        if prev is None:
            del _state.rules
        else:
            _state.rules = prev


def resolve(*logical: Optional[str]) -> P:
    """Resolve logical axis names to a PartitionSpec under current rules."""
    rules = get_rules()
    out, used = [], set()
    for name in logical:
        if name is None:
            out.append(None)
            continue
        binding = rules.get(name)
        if binding is None:
            out.append(None)
            continue
        axes = (binding,) if isinstance(binding, str) else tuple(binding)
        fresh = tuple(a for a in axes if a not in used)
        used.update(fresh)
        if not fresh:
            out.append(None)
        elif len(fresh) == 1:
            out.append(fresh[0])
        else:
            out.append(fresh)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def shard(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """with_sharding_constraint under the current logical rules.

    No-op outside a mesh context so model code runs unmodified on a bare CPU.
    """
    spec = mesh_spec(x.shape, *logical)
    return x if spec is None else jax.lax.with_sharding_constraint(x, spec)


def mesh_spec(shape: Tuple[int, ...], *logical: Optional[str]) -> Optional[P]:
    """The PartitionSpec ``shard`` constrains a ``shape`` array to under
    the mesh in scope and the current rules; None when no mesh is in scope.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return None
    spec = resolve(*logical)
    # Drop bindings to axes the active mesh doesn't have.
    names = set(mesh.axis_names)

    def keep(e):
        if e is None:
            return None
        if isinstance(e, tuple):
            kept = tuple(a for a in e if a in names)
            return kept if kept else None
        return e if e in names else None

    spec = P(*(keep(e) for e in spec))
    # Never shard a dim that isn't divisible by its mesh extent.
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    fixed = []
    for dim, e in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if e is None:
            fixed.append(None)
            continue
        extent = 1
        for a in (e,) if isinstance(e, str) else e:
            extent *= sizes[a]
        fixed.append(e if dim % extent == 0 and dim >= extent else None)
    return P(*fixed)


# ---------------------------------------------------------------------------
# Parameter partition-spec inference (name-based, t5x-style).
# Order matters: first match wins. Specs are in LOGICAL names; leading layer-
# stacking dims are padded with None.
# ---------------------------------------------------------------------------
_PARAM_RULES: Sequence[Tuple[str, Tuple[Optional[str], ...]]] = (
    (r"experts/w_down$", ("ep", None, "fsdp")),
    (r"experts/(w_gate|w_up)$", ("ep", "fsdp", None)),
    # embed: vocab rows replicated, d sharded on tp — a gather over a
    # vocab-sharded table forces SPMD "involuntary full rematerialization"
    (r"(^|/)embed$", (None, "tp")),
    (r"pos_embed$", (None, None)),
    (r"(wq|wk|wv|wqkv)$", ("fsdp", "tp")),
    (r"(wq_b|wkv_b)$", (None, "tp")),
    (r"(wq_a|wkv_a)$", ("fsdp", None)),
    (r"wo$", ("tp", "fsdp")),
    (r"(w_gate|w_up|w_in|w_r|w_k|w_v|w_g|w_kc|w_rc|router|w_cross_kv)$", ("fsdp", "tp")),
    (r"(w_down|w_out|w_o|w_vc)$", ("tp", "fsdp")),
    (r"w_decay_a$", ("fsdp", None)),
    (r"w_decay_b$", (None, "tp")),
    (r"lm_head$", (None, "tp")),
    (r"(conv|kernel)$", (None, None, None, "tp")),
    (r".*", ()),  # scales, biases, gates, A_log, D, dt_bias -> replicated
)


def _spec_for(path: str, ndim: int) -> P:
    for pat, logical in _PARAM_RULES:
        if re.search(pat, path):
            spec = list(logical)
            break
    spec = spec[:ndim]
    spec = [None] * (ndim - len(spec)) + spec
    return resolve(*spec)


def param_specs(params) -> "jax.tree_util.PyTreeDef":
    """PartitionSpec pytree matching ``params`` under the current rules."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    paths = ["/".join(str(getattr(k, "key", k)) for k in kp) for kp, _ in flat]
    leaves = [_spec_for(p, v.ndim) for p, (_, v) in zip(paths, flat)]
    treedef = jax.tree_util.tree_structure(params)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def mesh_safe_specs(params, mesh) -> "jax.tree_util.PyTreeDef":
    """param_specs with axes dropped where sizes don't divide."""
    specs = param_specs(params)
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes if hasattr(mesh, "axis_sizes") else mesh.devices.shape))
    names = set(mesh.axis_names)

    def fix(v, spec):
        entries = tuple(spec) + (None,) * (v.ndim - len(spec))
        fixed = []
        for dim, e in zip(v.shape, entries):
            if e is None:
                fixed.append(None)
                continue
            axes = (e,) if isinstance(e, str) else tuple(a for a in e)
            axes = tuple(a for a in axes if a in names)
            extent = 1
            for a in axes:
                extent *= sizes[a]
            if not axes or extent == 1 or dim % extent != 0:
                fixed.append(None)
            elif len(axes) == 1:
                fixed.append(axes[0])
            else:
                fixed.append(axes)
        while fixed and fixed[-1] is None:
            fixed.pop()
        return P(*fixed)

    return jax.tree_util.tree_map(fix, params, specs)
