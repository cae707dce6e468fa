"""The layer-pattern hybrid (granite-4.0-h: Mamba2 and attention layers by
``layer_types``, each followed by a SwiGLU MLP) as the benchmark sees it:
its sizes read from a configuration file (``dims``), seeded weights
(``weights``), a plain reference (``reference``) and the model FLOPs of a
training step (``counts``). Nothing here imports the program."""
from __future__ import annotations

import dataclasses
import itertools
from typing import List, Tuple


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of a configuration file's top level (Hugging Face keys)."""
    d: int
    ff: int
    vocab: int
    heads: int
    kv_heads: int
    head_dim: int
    layer_types: Tuple[str, ...]
    ssm_heads: int
    ssm_head_dim: int
    d_state: int
    groups: int
    conv_width: int
    chunk: int
    eps: float
    emb_mult: float
    attn_scale: float
    res_mult: float
    logit_div: float

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        heads = cfg["num_attention_heads"]
        return cls(
            d=cfg["hidden_size"], ff=cfg["shared_intermediate_size"],
            vocab=cfg["vocab_size"], heads=heads,
            kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg.get("head_dim") or cfg["hidden_size"] // heads,
            layer_types=tuple(cfg["layer_types"]),
            ssm_heads=cfg["mamba_n_heads"], ssm_head_dim=cfg["mamba_d_head"],
            d_state=cfg["mamba_d_state"], groups=cfg["mamba_n_groups"],
            conv_width=cfg["mamba_d_conv"], chunk=cfg["mamba_chunk_size"],
            eps=cfg["rms_norm_eps"], emb_mult=cfg["embedding_multiplier"],
            attn_scale=cfg["attention_multiplier"],
            res_mult=cfg["residual_multiplier"], logit_div=cfg["logits_scaling"])

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_channels(self) -> int:
        """x, B and C: the channels the causal conv runs over."""
        return self.d_inner + 2 * self.groups * self.d_state

    @property
    def in_proj(self) -> int:
        """z, x, B, C and dt: the Mamba2 input projection's outputs."""
        return self.d_inner + self.conv_channels + self.ssm_heads

    def runs(self) -> List[Tuple[str, int]]:
        """(kind, count) of each run of consecutive layers of one kind."""
        return [(k, len(list(g))) for k, g in itertools.groupby(self.layer_types)]
