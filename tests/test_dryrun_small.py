"""Small-mesh dry-run in a subprocess (device count must be set pre-jax-init).

Proves the lower+compile path works for a reduced config on a (2,2,2)
pod/data/model mesh — the CI-scale version of the 2x16x16 production dry-run.
"""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp

from repro.configs import REGISTRY
from repro.core.choices import MeshChoice
from repro.core.profiler import roofline_from_compiled
from repro.launch.specs import batch_shardings, batch_specs, param_shardings, replicated
from repro.launch.steps import build_train_step, init_train_state
from repro.models.registry import build_model
from repro.models.sharding import axis_rules
from repro.optim.optimizers import sgd

arch = %r
cfg = REGISTRY[arch].reduced()
choice = MeshChoice((2, 2, 2), ("pod", "data", "model"), microbatch=2, remat="dots")
from repro.launch.mesh import make_mesh
mesh = make_mesh(choice.mesh_shape, choice.axis_names)
rules = choice.rules()
model = build_model(cfg, impl="chunked", chunk=8, remat=choice.remat)
params_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
opt = sgd()
step = build_train_step(model, opt, microbatch=choice.microbatch)
state_sds = {"params": params_sds, "opt": (), "err": (),
             "step": jax.ShapeDtypeStruct((), jnp.int32)}

class Shape:
    global_batch, seq_len, mode = 8, 16, "train"
    name = "tiny"

with jax.set_mesh(mesh):
    with axis_rules(rules):
        p_shard = param_shardings(params_sds, mesh, rules)
        state_shard = {"params": p_shard, "opt": (), "err": (), "step": replicated(mesh)}
        batch_sds = batch_specs(cfg, Shape)
        b_shard = batch_shardings(batch_sds, mesh, rules)
        lowered = jax.jit(step, in_shardings=(state_shard, b_shard),
                          out_shardings=(state_shard, {"loss": replicated(mesh),
                                                       "grad_norm": replicated(mesh)}),
                          donate_argnums=(0,)).lower(state_sds, batch_sds)
        compiled = lowered.compile()
        terms = roofline_from_compiled(compiled, compiled.as_text(), choice.n_chips)
print(json.dumps({"ok": True, "flops": terms.flops, "coll": terms.collective_bytes,
                  "mem": terms.per_device_memory}))
"""


@pytest.mark.parametrize("arch", ["llama3.2-1b", "deepseek-moe-16b", "rwkv6-7b"])
def test_small_mesh_dryrun(arch):
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", SCRIPT % arch], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    last = [l for l in out.stdout.splitlines() if l.startswith("{")][-1]
    rec = json.loads(last)
    assert rec["ok"] and rec["flops"] > 0
