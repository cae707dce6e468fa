"""Mixture-of-Experts layer (fine-grained, shared + routed top-k, capacity drop).

TPU-native EP design (DESIGN.md §5): activations are replicated across the
``tp``/``ep`` mesh axis (they are only batch-sharded), so expert *dispatch is
communication-free* — each EP rank locally gathers the tokens routed to its
resident experts — and *combine is a single psum* over the EP axis, the same
collective a TP MLP would need anyway. No all-to-all. Over-capacity tokens are
dropped per expert (Switch-style); capacity_factor configures the slack.

Two execution paths:
  - mesh path: shard_map manual over (pod, data, ep) axes;
  - local path: identical math on one device (smoke tests / no mesh).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import activate, dense_init
from repro.models.sharding import get_rules, resolve


def moe_params(key, cfg, dtype=jnp.float32):
    E, d, ff = cfg.n_experts, cfg.d_model, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    scale = 1.0 / (d ** 0.5)
    p = {
        "router": dense_init(ks[0], d, E, dtype),
        "experts": {
            "w_gate": (jax.random.normal(ks[1], (E, d, ff)) * scale).astype(dtype),
            "w_up": (jax.random.normal(ks[2], (E, d, ff)) * scale).astype(dtype),
            "w_down": (jax.random.normal(ks[3], (E, ff, d)) * (1.0 / ff ** 0.5)).astype(dtype),
        },
    }
    if cfg.n_shared_experts:
        sff = cfg.n_shared_experts * ff
        kg, ku, kd = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": dense_init(kg, d, sff, dtype),
            "w_up": dense_init(ku, d, sff, dtype),
            "w_down": dense_init(kd, sff, d, dtype),
        }
    return p


def _route(x_flat, router_w, cfg):
    """Token-choice top-k routing. Returns dense gates (T,E) and aux loss."""
    logits = (x_flat @ router_w).astype(jnp.float32)  # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_g, top_i = jax.lax.top_k(probs, cfg.top_k)
    top_g = top_g / jnp.maximum(top_g.sum(-1, keepdims=True), 1e-9)  # renorm
    T = x_flat.shape[0]
    gates = jnp.zeros_like(probs).at[jnp.arange(T)[:, None], top_i].set(top_g)
    # switch-style load-balance aux: E * sum_e f_e * P_e
    f = (gates > 0).astype(jnp.float32).mean(0)  # fraction routed to e
    pmean = probs.mean(0)
    aux = cfg.n_experts * jnp.sum(f * pmean) * cfg.router_aux_coef
    return gates, aux


def _expert_compute(xb, w_gate, w_up, w_down, activation):
    """xb: (E_loc, C, d) -> (E_loc, C, d)."""
    h = activate(jnp.einsum("ecd,edf->ecf", xb, w_gate), activation)
    h = h * jnp.einsum("ecd,edf->ecf", xb, w_up)
    return jnp.einsum("ecf,efd->ecd", h, w_down)


def _moe_local(x_flat, gates, experts, cfg, capacity: int, e_offset: int, e_local: int,
               activation: str):
    """Compute routed output for experts [e_offset, e_offset+e_local).

    Per local expert, select its top-``capacity`` tokens by gate value
    (over-capacity tokens are dropped), run the expert MLP, and scatter-add
    the gated results back to token order. All ops are local to the shard.
    """
    T, d = x_flat.shape
    my_gates = jax.lax.dynamic_slice_in_dim(gates, e_offset, e_local, axis=1)  # (T,E_loc)
    cap = min(capacity, T)
    sel_g, sel_i = jax.lax.top_k(my_gates.T, cap)  # (E_loc, C)
    xb = jnp.take(x_flat, sel_i.reshape(-1), axis=0).reshape(e_local, cap, d)
    yb = _expert_compute(xb, experts["w_gate"], experts["w_up"], experts["w_down"], activation)
    yb = yb * sel_g[..., None].astype(yb.dtype)  # gate==0 rows contribute nothing
    y = jnp.zeros((T, d), yb.dtype).at[sel_i.reshape(-1)].add(yb.reshape(-1, d))
    return y


def _capacity(T: int, cfg, capacity_factor: float, min_capacity: int) -> int:
    cap = math.ceil(T * cfg.top_k / cfg.n_experts * capacity_factor)
    return min(T, max(min_capacity, cap))


def apply_moe(p, x, cfg, capacity_factor: float = 1.25, mesh=None,
              activation: Optional[str] = None, min_capacity: int = 4):
    """x: (B,S,d) -> (y, aux_loss). Over-capacity tokens are dropped
    (Switch-style); min_capacity keeps small decode batches drop-free."""
    act = activation or cfg.activation
    B_, S, d = x.shape
    rules = get_rules()
    ep_axes = rules.get("ep")
    if isinstance(ep_axes, str):
        ep_axes = (ep_axes,)
    if mesh is None:
        amesh = jax.sharding.get_abstract_mesh()
        mesh = None if amesh.empty else amesh
    ep_axes = tuple(a for a in (ep_axes or ()) if mesh is not None and a in mesh.axis_names)

    def shared_out(x_flat):
        if "shared" not in p:
            return 0.0
        h = activate(x_flat @ p["shared"]["w_gate"], act)
        h = h * (x_flat @ p["shared"]["w_up"])
        return h @ p["shared"]["w_down"]

    if mesh is None or not ep_axes:
        # single-shard path
        x_flat = x.reshape(B_ * S, d)
        gates, aux = _route(x_flat, p["router"], cfg)
        capacity = _capacity(B_ * S, cfg, capacity_factor, min_capacity)
        y = _moe_local(x_flat, gates, p["experts"], cfg, capacity, 0, cfg.n_experts, act)
        y = y + shared_out(x_flat)
        return y.reshape(B_, S, d).astype(x.dtype), aux

    # --- mesh path: manual over (batch axes) x (ep axes) -------------------
    batch_axes = rules.get("batch") or ()
    if isinstance(batch_axes, str):
        batch_axes = (batch_axes,)
    batch_axes = tuple(a for a in batch_axes if a in mesh.axis_names)
    manual = batch_axes + ep_axes
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    ep_size = 1
    for a in ep_axes:
        ep_size *= sizes[a]
    dp_size = 1
    for a in batch_axes:
        dp_size *= sizes[a]
    E = cfg.n_experts
    assert E % ep_size == 0, f"n_experts={E} must divide ep={ep_size}"
    e_local = E // ep_size
    T_local = (B_ // dp_size) * S
    capacity = _capacity(T_local, cfg, capacity_factor, min_capacity)

    x_spec = P(batch_axes if len(batch_axes) > 1 else (batch_axes[0] if batch_axes else None))
    # ---- wide-EP path (deepseek-v3-class; choice.wide_ep binds "ep" to
    # ("model","data")): experts live sharded over the FULL grid and TOKENS
    # move (all-gather over the data overlap + reduce-scatter back) instead of
    # weights — no full-d weight materialization, no per-layer FSDP gather of
    # ~650B expert parameters.
    token_axes = tuple(a for a in ep_axes if a in batch_axes)
    pure_ep = tuple(a for a in ep_axes if a not in batch_axes)
    if token_axes and E % ep_size == 0:
        return _apply_moe_wide_ep(p, x, cfg, mesh, rules, batch_axes, pure_ep,
                                  token_axes, sizes, capacity_factor,
                                  min_capacity, act, x_spec)

    ep_spec0 = ep_axes if len(ep_axes) > 1 else ep_axes[0]
    # fsdp axes the expert weights are STORED sharded on (d / ff dims). The
    # in_specs must match the stored sharding exactly: a mismatched spec makes
    # XLA reshard the whole STACKED weight tensor at the enclosing scan
    # boundary (observed: ~40GB live for deepseek-v3). The FSDP all-gather
    # happens inside, per layer, so only one layer's weights are ever full.
    rules = get_rules()
    fsdp_axes = rules.get("fsdp")
    if isinstance(fsdp_axes, str):
        fsdp_axes = (fsdp_axes,)
    fsdp_axes = tuple(a for a in (fsdp_axes or ()) if a in mesh.axis_names
                      and a not in ep_axes)
    fsdp_spec = (fsdp_axes if len(fsdp_axes) > 1 else
                 (fsdp_axes[0] if fsdp_axes else None))
    manual = tuple(dict.fromkeys(batch_axes + ep_axes + fsdp_axes))
    expert_specs = {
        "w_gate": P(ep_spec0, fsdp_spec), "w_up": P(ep_spec0, fsdp_spec),
        "w_down": P(ep_spec0, None, fsdp_spec),
    }
    shared_spec = {k: P(fsdp_spec, ep_spec0) if k != "w_down" else P(ep_spec0, fsdp_spec)
                   for k in p.get("shared", {})}
    in_specs = (x_spec, P(), expert_specs)
    args = (x, p["router"], p["experts"])
    if "shared" in p:
        in_specs = in_specs + (shared_spec,)
        args = args + (p["shared"],)

    def fn(x_loc, router_w, experts_loc, *maybe_shared):
        Bl, Sl, _ = x_loc.shape
        x_flat = x_loc.reshape(Bl * Sl, d)
        gates, aux = _route(x_flat, router_w, cfg)
        ep_index = 0
        for a in ep_axes:
            ep_index = ep_index * sizes[a] + jax.lax.axis_index(a)
        if fsdp_axes:  # per-layer FSDP unshard of this rank's experts
            experts_loc = {
                "w_gate": jax.lax.all_gather(experts_loc["w_gate"], fsdp_axes,
                                             axis=1, tiled=True),
                "w_up": jax.lax.all_gather(experts_loc["w_up"], fsdp_axes,
                                           axis=1, tiled=True),
                "w_down": jax.lax.all_gather(experts_loc["w_down"], fsdp_axes,
                                             axis=2, tiled=True),
            }
        y = _moe_local(x_flat, gates, experts_loc, cfg, capacity,
                       ep_index * e_local, e_local, act)
        if maybe_shared:
            sh = maybe_shared[0]
            if fsdp_axes:
                sh = {"w_gate": jax.lax.all_gather(sh["w_gate"], fsdp_axes, axis=0, tiled=True),
                      "w_up": jax.lax.all_gather(sh["w_up"], fsdp_axes, axis=0, tiled=True),
                      "w_down": jax.lax.all_gather(sh["w_down"], fsdp_axes, axis=1, tiled=True)}
            h = activate(x_flat @ sh["w_gate"], act)
            h = h * (x_flat @ sh["w_up"])
            y = y + h @ sh["w_down"]
        y = jax.lax.psum(y, ep_axes)  # combine across expert shards
        aux = jax.lax.pmean(aux, manual)
        return y.reshape(Bl, Sl, d).astype(x_loc.dtype), aux

    y, aux = jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs,
        out_specs=(x_spec, P()), check_vma=False,
        axis_names=set(manual))(*args)
    return y, aux


def _apply_moe_wide_ep(p, x, cfg, mesh, rules, batch_axes, ep_axes, fsdp_axes,
                       sizes, capacity_factor, min_capacity, act, x_spec):
    """Wide expert parallelism: experts sharded over (ep x fsdp) axes jointly.

    Each device owns E/(ep*fsdp) complete experts (full d x ff). Tokens are
    all-gathered over the fsdp(data) axes, every device computes its own
    experts' top-capacity tokens, and results return via reduce-scatter over
    data + psum over the ep axis. Collectives move activations (O(T*d)), not
    weights (O(E*d*ff)) — the right trade at deepseek-v3 scale.
    """
    B_, S, d = x.shape
    E = cfg.n_experts
    wide_axes = ep_axes + fsdp_axes
    wide_size = 1
    for a in wide_axes:
        wide_size *= sizes[a]
    e_local = E // wide_size
    dp_size = 1
    for a in batch_axes:
        dp_size *= sizes[a]
    fsdp_size = 1
    for a in fsdp_axes:
        fsdp_size *= sizes[a]
    T_local = (B_ // dp_size) * S
    T_wide = T_local * fsdp_size
    capacity = _capacity(T_wide, cfg, capacity_factor, min_capacity)
    ep_spec0 = ep_axes if len(ep_axes) > 1 else ep_axes[0]
    wide_spec = wide_axes if len(wide_axes) > 1 else wide_axes[0]

    expert_specs = {"w_gate": P(wide_spec), "w_up": P(wide_spec),
                    "w_down": P(wide_spec)}
    fsdp_spec = fsdp_axes if len(fsdp_axes) > 1 else fsdp_axes[0]
    shared_spec = {k: P(fsdp_spec, ep_spec0) if k != "w_down" else P(ep_spec0, fsdp_spec)
                   for k in p.get("shared", {})}
    in_specs = (x_spec, P(), expert_specs)
    args = (x, p["router"], p["experts"])
    if "shared" in p:
        in_specs = in_specs + (shared_spec,)
        args = args + (p["shared"],)
    manual = tuple(dict.fromkeys(batch_axes + ep_axes + fsdp_axes))

    def fn(x_loc, router_w, experts_loc, *maybe_shared):
        Bl, Sl, _ = x_loc.shape
        x_flat = x_loc.reshape(Bl * Sl, d)
        gates, aux = _route(x_flat, router_w, cfg)
        # gather tokens + gates across the data shards
        x_wide = jax.lax.all_gather(x_flat, fsdp_axes, axis=0, tiled=True)
        g_wide = jax.lax.all_gather(gates, fsdp_axes, axis=0, tiled=True)
        # global expert index of this device's slice
        idx = 0
        for a in wide_axes:
            idx = idx * sizes[a] + jax.lax.axis_index(a)
        y_wide = _moe_local(x_wide, g_wide, experts_loc, cfg, capacity,
                            idx * e_local, e_local, act)
        # combine: reduce-scatter tokens back to their data shard, then sum
        # expert contributions across the ep axis
        y = jax.lax.psum_scatter(y_wide, fsdp_axes, scatter_dimension=0, tiled=True)
        y = jax.lax.psum(y, ep_axes)
        if maybe_shared:
            sh = maybe_shared[0]
            sh = {"w_gate": jax.lax.all_gather(sh["w_gate"], fsdp_axes, axis=0, tiled=True),
                  "w_up": jax.lax.all_gather(sh["w_up"], fsdp_axes, axis=0, tiled=True),
                  "w_down": jax.lax.all_gather(sh["w_down"], fsdp_axes, axis=1, tiled=True)}
            h = activate(x_flat @ sh["w_gate"], act)
            h = h * (x_flat @ sh["w_up"])
            y = y + jax.lax.psum(h @ sh["w_down"], ep_axes)
        aux = jax.lax.pmean(aux, manual)
        return y.reshape(Bl, Sl, d).astype(x_loc.dtype), aux

    y, aux = jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs,
        out_specs=(x_spec, P()), check_vma=False,
        axis_names=set(manual))(*args)
    return y, aux
