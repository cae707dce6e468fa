"""Compile the main path's Pallas kernels for a described TPU v5e, no chip.

Interpret mode on the CPU does not apply Mosaic's rules (a block's last two
dims must divide by (8, 128) or equal the array's), so these tests hand the
kernels to the TPU compiler at granite-3-2b geometry (32 query heads, 8 KV
heads, head dim 64) and at deepseek-v3's latent geometry for MLA decode.
Nothing runs: a compile that the chip's compiler would refuse fails here.

The topology is described inside a module fixture, never at import: only one
process may load the TPU library, and the test workers all import this file.
"""
from __future__ import annotations

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.kernels import flash_attention as fa
from repro.kernels import ops
from repro.kernels import rmsnorm as rn

H, K, HD = 32, 8, 64          # granite-3-2b attention geometry
B, SMAX, BS, NB = 4, 544, 16, 137  # 4 slots, prompt 512 + gen 32, paged


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    """Compile ``fn`` for the described chip; returns the HLO text."""
    return jax.jit(fn).lower(*args).compile().as_text()


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_flash_attention_fwd_bwd_compiles(one_chip):
    q = _sds(one_chip, (1, H, 2048, HD))

    def loss(q, k, v):
        return fa.flash_attention_mha(q, k, v, interpret=False).sum()

    hlo = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    assert hlo.count("tpu_custom_call") == 3  # fwd, dq, dkv


@pytest.mark.parametrize("hd", [64, 128])
def test_flash_attention_train_shape_compiles(one_chip, hd):
    """The forward, dq and dkv kernels at granite-train-2k's attention shape
    (batch 2, 32 heads, 2048 positions), under the plan the shapes give."""
    q = _sds(one_chip, (2, H, 2048, hd))

    def loss(q, k, v):
        return fa.flash_attention_mha(q, k, v, interpret=False).sum()

    hlo = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    assert hlo.count("tpu_custom_call") == 3  # fwd, dq, dkv


def test_kernel_names_are_hlo_instruction_names(one_chip, monkeypatch):
    """A kernel's ``name`` becomes its instruction's name in the compiled
    program, which is the op name a device profile keys its time by: the
    train step at granite-3-2b widths (two layers) holds the three flash
    kernels, the paged decode program its kernel."""
    from repro.configs import get_config
    from repro.engine.rungs import Rung
    from repro.launch.steps import init_train_state
    from repro.optim.optimizers import adam

    monkeypatch.setattr("repro.kernels.backend.default_interpret",
                        lambda: False)
    cfg = dataclasses.replace(get_config("granite-3-2b"), n_layers=2)
    rung, opt = Rung(name="r", attn_impl="pallas"), adam()
    state = jax.eval_shape(lambda: init_train_state(
        rung.build_model(cfg), opt, jax.random.PRNGKey(0)))
    state = jax.tree_util.tree_map(
        lambda a: _sds(one_chip, a.shape, a.dtype), state)
    batch = {"tokens": _sds(one_chip, (1, 512), jnp.int32)}
    train = rung.jitted_step(cfg, opt).lower(state, batch).compile().as_text()
    for name in ("flash_fwd", "flash_dq", "flash_dkv"):
        assert re.search(rf"%{name}\.\d+ = .*tpu_custom_call", train), name
    s = lambda *a: _sds(one_chip, *a)  # noqa: E731
    pool = s((NB, K, BS, HD))
    decode = _compile(lambda q, k, v, t, n: fa.flash_decode_paged(
        q, k, v, t, n, interpret=False), s((B, K, H // K, HD)), pool, pool,
        s((B, SMAX // BS), jnp.int32), s((B,), jnp.int32))
    assert re.search(r"%flash_decode_paged\.\d+ = .*tpu_custom_call", decode)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("kernel", ["decode", "decode_paged", "spec",
                                    "spec_paged"])
def test_decode_kernels_compile(one_chip, kernel, dtype):
    s = lambda *a: _sds(one_chip, *a)  # noqa: E731
    lengths = s((B,), jnp.int32)
    table = s((B, SMAX // BS), jnp.int32)
    cache = s((B, K, SMAX, HD), dtype)
    pool = s((NB, K, BS, HD), dtype)
    q = s((B, K, H // K, HD))
    q_spec = s((B, K, 4, H // K, HD))  # 4 draft positions
    calls = {
        "decode": (lambda q, k, v, n: fa.flash_decode(
            q, k, v, n, interpret=False), (q, cache, cache, lengths)),
        "decode_paged": (lambda q, k, v, t, n: fa.flash_decode_paged(
            q, k, v, t, n, interpret=False), (q, pool, pool, table, lengths)),
        "spec": (lambda q, k, v, n: fa.flash_decode_spec(
            q, k, v, n, interpret=False), (q_spec, cache, cache, lengths)),
        "spec_paged": (lambda q, k, v, t, n: fa.flash_decode_spec_paged(
            q, k, v, t, n, interpret=False),
            (q_spec, pool, pool, table, lengths)),
    }
    fn, args = calls[kernel]
    assert "tpu_custom_call" in _compile(fn, *args)


@pytest.mark.parametrize("paged", [False, True])
def test_mla_decode_compiles(one_chip, paged, monkeypatch):
    """MLA decodes through the same kernel with one latent KV head (K=1)."""
    monkeypatch.setattr("repro.kernels.backend.default_interpret",
                        lambda: False)
    s = lambda *a: _sds(one_chip, *a)  # noqa: E731
    q_lat, q_rope = s((B, 1, 128, 512)), s((B, 1, 128, 64))
    lengths = s((B,), jnp.int32)
    if paged:
        hlo = _compile(lambda *a: ops.decode_attention_mla_paged(
            *a, scale=0.07), q_lat, q_rope, s((NB, BS, 512)), s((NB, BS, 64)),
            s((B, SMAX // BS), jnp.int32), lengths)
    else:
        hlo = _compile(lambda *a: ops.decode_attention_mla(*a, scale=0.07),
                       q_lat, q_rope, s((B, SMAX, 512)), s((B, SMAX, 64)),
                       lengths)
    assert "tpu_custom_call" in hlo


def test_rmsnorm_fwd_bwd_compiles(one_chip):
    x, scale = _sds(one_chip, (4096, 2048)), _sds(one_chip, (2048,))
    fwd = _compile(lambda x, s: rn.rmsnorm(x, s, interpret=False), x, scale)
    bwd = _compile(jax.grad(lambda x, s: rn.rmsnorm(
        x, s, interpret=False).sum(), argnums=(0, 1)), x, scale)
    assert "tpu_custom_call" in fwd and "tpu_custom_call" in bwd


def test_mesh_train_step_compiles_with_pallas_attention(topo, monkeypatch):
    """The flash kernels on a 2x2 (data, model) mesh: XLA cannot partition a
    Mosaic kernel, so attention runs per shard under ``shard_map``."""
    monkeypatch.setattr("repro.kernels.backend.default_interpret",
                        lambda: False)
    from repro.configs import get_config
    from repro.engine.rungs import Rung
    from repro.launch.steps import init_train_state
    from repro.models.sharding import mesh_safe_specs
    from repro.optim.optimizers import adam

    cfg = dataclasses.replace(get_config("granite-3-2b").reduced(),
                              n_layers=2)
    rung = Rung(name="mesh", attn_impl="pallas", mesh_shape=(2, 2))
    opt = adam()
    model = rung.build_model(cfg)
    mesh = Mesh(np.array(topo.devices[:4]).reshape(2, 2), ("data", "model"))
    state = jax.eval_shape(
        lambda: init_train_state(model, opt, jax.random.PRNGKey(0)))
    state = jax.tree_util.tree_map(
        lambda a, spec: _sds(NamedSharding(mesh, spec), a.shape, a.dtype),
        state, mesh_safe_specs(state, mesh))
    batch = {"tokens": _sds(NamedSharding(mesh, PartitionSpec()), (4, 128),
                            jnp.int32)}
    with jax.set_mesh(mesh):
        hlo = rung.jitted_step(cfg, opt).lower(state, batch).compile().as_text()
    assert hlo.count("tpu_custom_call") == 3


def test_hybrid_train_step_compiles_at_the_cell_shape(one_chip, monkeypatch):
    """granite4h-train-8k's step: one period of granite-4.0-h-micro (5
    Mamba2, 1 attention, 4 Mamba2 layers), the 12,544-row vocabulary slice,
    1 x 8192 tokens, full remat, f32 weights and Adam. The compiler refuses
    a step that does not fit the chip's memory."""
    from repro.configs import get_config
    from repro.engine.rungs import Rung
    from repro.launch.steps import init_train_state
    from repro.optim.optimizers import adam

    monkeypatch.setattr("repro.kernels.backend.default_interpret",
                        lambda: False)
    full = get_config("granite-4.0-h-micro")
    cfg = dataclasses.replace(full, n_layers=10, layer_types=full.layer_types[:10],
                              vocab_size=12544)
    rung, opt = Rung(name="r", attn_impl="pallas", remat="full"), adam()
    state = jax.eval_shape(lambda: init_train_state(
        rung.build_model(cfg), opt, jax.random.PRNGKey(0)))
    state = jax.tree_util.tree_map(
        lambda a: _sds(one_chip, a.shape, a.dtype), state)
    batch = {"tokens": _sds(one_chip, (1, 8192), jnp.int32)}
    compiled = rung.jitted_step(cfg, opt).lower(state, batch).compile()
    train = compiled.as_text()
    for name in ("flash_fwd", "flash_dq", "flash_dkv"):
        assert re.search(rf"%{name}\.\d+ = .*tpu_custom_call", train), name
    # weights, Adam's m and v: 772.2M parameters x 12 bytes
    assert compiled.memory_analysis().argument_size_in_bytes > 9.2e9
