"""Config registry: ``get_config("<arch-id>")`` / ``--arch <id>`` resolution."""
from __future__ import annotations

from repro.configs.base import ModelConfig
from repro.configs.shapes import SHAPES, InputShape, applicable  # noqa: F401

from repro.configs.whisper_small import CONFIG as _whisper
from repro.configs.zamba2_2p7b import CONFIG as _zamba2
from repro.configs.llama3p2_1b import CONFIG as _llama1b
from repro.configs.granite3_2b import CONFIG as _granite
from repro.configs.command_r_35b import CONFIG as _commandr
from repro.configs.nemotron4_15b import CONFIG as _nemotron
from repro.configs.llama3p2_vision_11b import CONFIG as _llamav
from repro.configs.deepseek_moe_16b import CONFIG as _dsmoe
from repro.configs.deepseek_v3_671b import CONFIG as _dsv3
from repro.configs.rwkv6_7b import CONFIG as _rwkv6
from repro.configs.granite4_h_micro import CONFIG as _granite4h
from repro.configs.cnn_paper import MOBILENET_V2, RESNET34, SHUFFLENET_V2

# The 10 assigned architectures (dry-run + roofline cells).
ASSIGNED: dict[str, ModelConfig] = {
    c.name: c
    for c in (
        _whisper, _zamba2, _llama1b, _granite, _commandr,
        _nemotron, _llamav, _dsmoe, _dsv3, _rwkv6,
    )
}

# The paper's own workloads (local + FL evaluation).
PAPER_MODELS: dict[str, ModelConfig] = {
    c.name: c for c in (RESNET34, MOBILENET_V2, SHUFFLENET_V2)
}

# Trained by the chip benchmark; not served (the slot engine holds no
# recurrent state yet), so outside the dry-run and decode sets above.
TRAIN_ONLY: dict[str, ModelConfig] = {c.name: c for c in (_granite4h,)}

REGISTRY: dict[str, ModelConfig] = {**ASSIGNED, **PAPER_MODELS, **TRAIN_ONLY}


def get_config(name: str) -> ModelConfig:
    if name not in REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(REGISTRY)}")
    return REGISTRY[name]
