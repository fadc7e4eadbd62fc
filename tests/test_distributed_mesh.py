"""Small-mesh SPMD integration: runs a subprocess with 8 forced host devices
(the device count is locked at first jax init, so these tests cannot share
the main pytest process, which must keep seeing 1 device)."""
import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.core.optimizers import prox_adam
from repro.distributed import sharding as shd
from repro.data.synthetic import TokenStreamConfig, token_batch
from repro.models.model_zoo import build
from repro.train.state import TrainState
from repro.train.step import make_train_step

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
model = build("qwen3-0.6b", reduced=True, remat=False)
cfg = model.cfg
opt = prox_adam(1e-3, lam=0.5)
data = TokenStreamConfig(vocab=cfg.vocab, seq_len=32, global_batch=4)

with shd.use_mesh(mesh):
    params = model.init(jax.random.PRNGKey(0))
    state = TrainState.create(params, opt)
    state_shd = shd.param_shardings(state, mesh)
    state = jax.device_put(state, state_shd)
    step = jax.jit(make_train_step(model, opt),
                   in_shardings=(state_shd, None),
                   out_shardings=(state_shd, None))
    losses = []
    for s in range(8):
        state, m = step(state, token_batch(data, s))
        losses.append(float(m["loss"]))

# single-device reference trajectory (same seeds): SPMD must match math
model2 = build("qwen3-0.6b", reduced=True, remat=False)
params2 = model2.init(jax.random.PRNGKey(0))
state2 = TrainState.create(params2, opt)
step2 = jax.jit(make_train_step(model2, opt))
losses2 = []
for s in range(8):
    state2, m2 = step2(state2, token_batch(data, s))
    losses2.append(float(m2["loss"]))

err = max(abs(a - b) for a, b in zip(losses, losses2))
w_sharded = np.asarray(jax.device_get(
    state.params["layers"]["b0_attn"]["mlp"]["wi"]))
w_single = np.asarray(state2.params["layers"]["b0_attn"]["mlp"]["wi"])
print(json.dumps({
    "loss_err": err,
    "param_err": float(np.max(np.abs(w_sharded - w_single))),
    "losses": losses,
    "n_devices": jax.device_count(),
}))
"""


@pytest.mark.slow
def test_spmd_training_matches_single_device(tmp_path):
    env = dict(os.environ,
               PYTHONPATH=os.path.abspath(
                   os.path.join(os.path.dirname(__file__), "..", "src")))
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["n_devices"] == 8
    assert result["loss_err"] < 2e-2, result
    assert result["param_err"] < 2e-2, result
    # training is actually progressing
    assert result["losses"][-1] < result["losses"][0]


_MOE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, json
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.distributed import sharding as shd
from repro.models import moe as moe_lib

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = get_config("olmoe-1b-7b").reduced()
cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
    cfg.moe, capacity_factor=float(cfg.moe.n_experts)))  # no-drop: exact
p = moe_lib.init_moe(jax.random.PRNGKey(0), cfg)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))

y_ref, aux_ref = jax.jit(
    lambda p, x: moe_lib.apply_moe(p, x, cfg, impl="gspmd"))(p, x)
with shd.use_mesh(mesh):
    y_sm, aux_sm = jax.jit(
        lambda p, x: moe_lib.apply_moe(p, x, cfg, impl="shard_map"))(p, x)

def loss(p, impl):
    with shd.use_mesh(mesh if impl == "shard_map" else None):
        y, aux = moe_lib.apply_moe(p, x, cfg, impl=impl)
    return jnp.sum(y ** 2) + aux["load_balance"]

g1 = jax.grad(lambda p: loss(p, "gspmd"))(p)
g2 = jax.grad(lambda p: loss(p, "shard_map"))(p)
rel = max(
    float(jnp.max(jnp.abs(a - b)) / jnp.maximum(jnp.max(jnp.abs(a)), 1e-9))
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)))
print(json.dumps({
    "y_err": float(jnp.max(jnp.abs(y_ref - y_sm))),
    "lb_err": abs(float(aux_ref["load_balance"])
                  - float(aux_sm["load_balance"])),
    "grad_rel_err": rel,
}))
"""


@pytest.mark.slow
def test_shard_map_moe_matches_gspmd():
    """Expert-parallel shard_map MoE == single-program GSPMD MoE (values,
    aux losses, grads) under a no-drop capacity."""
    env = dict(os.environ,
               PYTHONPATH=os.path.abspath(
                   os.path.join(os.path.dirname(__file__), "..", "src")))
    out = subprocess.run([sys.executable, "-c", _MOE_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["y_err"] < 1e-4, result
    assert result["lb_err"] < 1e-5, result
    assert result["grad_rel_err"] < 1e-5, result
