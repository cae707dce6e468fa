"""Rung: an *executable* ladder entry.

The Swan planner's pruned ladder (core/cost.py) is a list of ChoiceProfiles —
passive cost-model objects. A Rung is the runnable counterpart: the knobs a
live session can actually switch mid-training (microbatch, attention kernel,
parameter dtype, mesh shape) plus a lazily-compiled-and-cached jitted train
step built from launch/steps.py. ``rungs_from_ladder`` maps a ChoiceProfile
ladder onto Rungs so the planner's output becomes directly runnable;
``default_rung_ladder`` builds a sensible downgrade ladder when no planner ran
(the CLI path).

Migration compatibility: two Rungs with the same ``mesh_shape`` can exchange
state in place (dtype changes go through launch.steps.cast_params); differing
mesh shapes require a checkpoint round-trip (session.py owns that).
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.cost import ChoiceProfile, ladder_sensitivities

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
           "float16": jnp.float16}


@dataclasses.dataclass
class Rung:
    """One executable execution choice. Fastest/costliest rungs sit at the
    top of a ladder; every field below is switchable at a migration."""
    name: str
    microbatch: int = 1
    attn_impl: str = "chunked"
    param_dtype: str = "float32"
    mesh_shape: Optional[Tuple[int, ...]] = None  # None = single-process jit
    chunk: int = 1024
    remat: str = "none"
    compression: str = "none"
    # fraction of a co-tenant's contention this rung still feels (1.0 = full
    # overlap with the contended resource; cheap rungs relinquish it)
    interference_sensitivity: float = 1.0
    # latency relative to the ladder head (used to scale calibrations onto
    # rungs that have never run) and an absolute planner estimate if one exists
    rel_latency: float = 1.0
    latency_estimate_s: Optional[float] = None

    def __post_init__(self):
        self._model = None
        self._model_key = None
        self._jitted = None
        self._jitted_key = None

    # -- identity ----------------------------------------------------------
    def signature(self) -> Tuple:
        return (self.microbatch, self.attn_impl, self.param_dtype,
                self.mesh_shape, self.chunk, self.remat, self.compression)

    @property
    def dtype(self):
        return _DTYPES[self.param_dtype]

    # -- construction ------------------------------------------------------
    @classmethod
    def from_mesh_choice(cls, choice, *, name: Optional[str] = None,
                         **overrides) -> "Rung":
        """Build a Rung from a core.choices.MeshChoice (or anything exposing
        ``rung_fields()``)."""
        fields = dict(choice.rung_fields())
        fields.update(overrides)
        return cls(name=name or getattr(choice, "name", "rung"), **fields)

    # -- executable surface ------------------------------------------------
    def build_model(self, cfg):
        """Model under this rung's kernel/dtype knobs (cached per config)."""
        from repro.models.registry import build_model
        key = (cfg.name, self.signature())
        if self._model is None or self._model_key != key:
            self._model = build_model(cfg, impl=self.attn_impl, chunk=self.chunk,
                                      remat=self.remat, param_dtype=self.dtype)
            self._model_key = key
        return self._model

    def train_step_fn(self, model, optimizer, *, lr: float = 0.05,
                      compressor=None):
        """The raw (unjitted) step — what dryrun lowers with explicit
        shardings and what ``jitted_step`` wraps for live execution."""
        from repro.launch.steps import build_train_step
        from repro.optim.compression import Compressor
        comp = compressor or Compressor(self.compression)
        return build_train_step(model, optimizer, microbatch=self.microbatch,
                                lr=lr, compressor=comp)

    def jitted_step(self, cfg, optimizer, *, lr: float = 0.05,
                    compressor=None):
        """Lazily-compiled cached jitted step: first call on a rung compiles,
        later calls (including after migrating away and back) reuse it.

        The state is donated: the new parameters and optimizer moments
        reuse the old buffers instead of doubling the state's footprint.
        Callers must keep only the returned state."""
        key = (cfg.name, self.signature(), optimizer.name, float(lr),
               getattr(compressor, "scheme", self.compression))
        if self._jitted is None or self._jitted_key != key:
            model = self.build_model(cfg)
            self._jitted = jax.jit(self.train_step_fn(
                model, optimizer, lr=lr, compressor=compressor),
                donate_argnums=0)
            self._jitted_key = key
        return self._jitted

    def invalidate(self):
        """Drop the compiled step (required after the device set changes —
        a remesh makes every cached executable stale)."""
        self._jitted = None
        self._jitted_key = None

    def profile(self, *, position: int = 0, n: int = 1) -> ChoiceProfile:
        """A ChoiceProfile view of this rung so SwanController (which walks
        ChoiceProfile ladders) can drive it directly."""
        lat = self.latency_estimate_s if self.latency_estimate_s is not None \
            else self.rel_latency
        return ChoiceProfile(choice=self, latency_s=lat, energy_j=lat,
                             power_w=1.0, cost_key=(n - position,))


def rungs_from_ladder(profiles: Sequence[ChoiceProfile], **overrides
                      ) -> List[Rung]:
    """Map a pruned ChoiceProfile ladder (fastest first, MeshChoice-backed)
    onto executable Rungs, preserving order; latency estimates come from the
    profiles and interference sensitivities from the cost model's ladder
    positions."""
    if not profiles:
        raise ValueError("empty ladder")
    sens = ladder_sensitivities(len(profiles))
    head_lat = profiles[0].latency_s
    out = []
    for i, p in enumerate(profiles):
        out.append(Rung.from_mesh_choice(
            p.choice, name=p.name,
            interference_sensitivity=sens[i],
            rel_latency=p.latency_s / max(head_lat, 1e-12),
            latency_estimate_s=p.latency_s, **overrides))
    return out


def default_rung_ladder(*, batch: int, microbatch: int = 1,
                        attn_impl: str = "chunked",
                        mesh_shape: Optional[Tuple[int, ...]] = None,
                        include_bf16: bool = True) -> List[Rung]:
    """Downgrade ladder for the CLI path (no planner run): each rung trades
    latency for relinquished burst compute — deeper gradient accumulation
    shrinks the per-microbatch working set, and the bottom rung additionally
    halves parameter memory traffic with bfloat16."""
    if microbatch < 1 or batch % microbatch:
        raise ValueError(f"microbatch {microbatch} does not divide batch "
                         f"{batch}; the accumulation reshape would fail")
    specs = [("full", microbatch, "float32", 1.00),
             ("accum", microbatch * 2, "float32", 1.15),
             ("lean", microbatch * 4, "bfloat16" if include_bf16 else "float32",
              1.35)]
    specs = [(n, mb, dt, rl) for n, mb, dt, rl in specs if batch % mb == 0]
    sens = ladder_sensitivities(len(specs))
    return [Rung(name=n, microbatch=mb, attn_impl=attn_impl, param_dtype=dt,
                 mesh_shape=mesh_shape, interference_sensitivity=s,
                 rel_latency=rl)
            for (n, mb, dt, rl), s in zip(specs, sens)]
