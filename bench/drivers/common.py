"""What the drivers share: the program's configuration and parameter tree
built from a configuration file and the benchmark's weights, the result a
driver hands back, and small statistics."""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional

import jax
import numpy as np

# configuration-file key -> the program's ModelConfig field
PROGRAM_FIELDS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "head_dim": "head_dim", "hidden_act": "activation", "norm": "norm",
    "tie_word_embeddings": "tie_embeddings", "rope_theta": "rope_theta",
}
PROGRAM_EPS = 1e-5  # the program's fixed norm epsilon


@dataclasses.dataclass
class Check:
    """One number compared with its limit: ``ok`` when value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value)) and self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    e2e: Dict[str, float]              # end-to-end metric -> value
    attempted: int
    failed: int
    checks: List[Check]
    memory_peak_bytes: Optional[int]
    record: dict                       # what the per-layer readers read
    problems: List[str] = dataclasses.field(default_factory=list)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and not self.problems and \
            all(c.ok for c in self.checks)


def program_config(cfg: dict):
    """The program's ModelConfig for a configuration file: the registry
    architecture with the file's sizes. Raises where the file states
    something the program cannot run."""
    from repro.configs import get_config
    m = cfg["model"]
    over = {PROGRAM_FIELDS[k]: v for k, v in m.items() if k in PROGRAM_FIELDS}
    hd = m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"]
    neutral = {"attention_multiplier": 1.0 / math.sqrt(hd),
               "embedding_multiplier": 1.0, "residual_multiplier": 1.0,
               "logits_scaling": 1.0}
    for k, v in neutral.items():
        if k in m and not math.isclose(m[k], v, rel_tol=1e-9):
            raise ValueError(f"the program applies no {k}; the file states {m[k]}")
    eps = m.get("rms_norm_eps", m.get("layer_norm_eps", PROGRAM_EPS))
    if eps != PROGRAM_EPS:
        raise ValueError(f"the program's norm epsilon is {PROGRAM_EPS}, the file states {eps}")
    pc = dataclasses.replace(get_config(cfg["arch"]), **over)
    for k, f in PROGRAM_FIELDS.items():
        if k in m and getattr(pc, f) != m[k]:
            raise ValueError(f"program {f}={getattr(pc, f)!r} but the file states {k}={m[k]!r}")
    return pc


def _norm(w, prefix):
    out = {"scale": w[prefix + ".scale"]}
    if prefix + ".bias" in w:
        out["bias"] = w[prefix + ".bias"]
    return out


def program_params(w: dict, model) -> dict:
    """The program's parameter tree holding the benchmark's arrays; checked
    leaf by leaf against the tree the program itself would build."""
    layers = {"ln1": _norm(w, "layers.attn_norm"), "ln2": _norm(w, "layers.mlp_norm"),
              "attn": {k: w["layers." + k] for k in ("wq", "wk", "wv", "wo")},
              "mlp": {k: w["layers." + k] for k in ("w_up", "w_down", "w_gate")
                      if "layers." + k in w}}
    p = {"embed": w["embed"], "ln_f": _norm(w, "final_norm"), "layers": layers}
    if "lm_head" in w:
        p["lm_head"] = w["lm_head"]
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got_s = jax.tree_util.tree_structure(p)
    want_s = jax.tree_util.tree_structure(want)
    if got_s != want_s:
        raise ValueError(f"parameter tree {got_s} is not the program's {want_s}")
    for a, b in zip(jax.tree_util.tree_leaves(p), jax.tree_util.tree_leaves(want)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"leaf {a.shape} {a.dtype} != program's {b.shape} {b.dtype}")
    return p


def canonical(p: dict) -> dict:
    """The inverse of :func:`program_params`: benchmark names for the
    program's leaves."""
    w = {"embed": p["embed"]}
    for name, tree in (("final_norm", p["ln_f"]), ("layers.attn_norm", p["layers"]["ln1"]),
                       ("layers.mlp_norm", p["layers"]["ln2"])):
        for k, v in tree.items():
            w[f"{name}.{k}"] = v
    for sub in ("attn", "mlp"):
        for k, v in p["layers"][sub].items():
            w["layers." + k] = v
    if "lm_head" in p:
        w["lm_head"] = p["lm_head"]
    return w


def quantile(values, q: float) -> float:
    """Linear-interpolated quantile where a missing value (``inf``) counts
    as the slowest; ``inf`` when the quantile reaches one."""
    v = np.sort(np.asarray(values, np.float64))
    if len(v) == 0:
        return float("inf")
    pos = q * (len(v) - 1)
    lo, hi = int(math.floor(pos)), int(math.ceil(pos))
    if not np.isfinite(v[hi]):
        return float("inf")
    return float(v[lo] + (v[hi] - v[lo]) * (pos - lo))


def peak_bytes(device) -> Optional[int]:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return None if peak is None else int(peak)
