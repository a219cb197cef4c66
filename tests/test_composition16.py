"""dp x tp x sp x ep composed in ONE mesh — the four-axis layout an
8-device factorization cannot reach (2x2x2x2 needs 16 devices).

The session-wide virtual mesh is 8 devices (conftest), so this runs in a
subprocess with 16 virtual CPU devices (same pattern as bench._DP8_CODE:
platform selection must happen before backend init). One full train step
of the MoE flagship with a ring-flash sp island, GQA + RoPE, tp-sharded
attention, ep-sharded experts — asserted AGAINST THE ORACLE: the same
math (dense attention, unsharded params) replicated on one device.
GSPMD sharding must be layout, never math.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

_CODE = r"""
import json
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 16)
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from distributed_pytorch_tpu import models, optim
from distributed_pytorch_tpu.ops.losses import cross_entropy_per_example
from distributed_pytorch_tpu.parallel import (make_gspmd_ring_attn_fn,
                                              make_spmd_train_step,
                                              shard_batch_spec)
from distributed_pytorch_tpu.parallel.tensor import shard_params
from distributed_pytorch_tpu.runtime import context

dp, tp, sp, ep = 2, 2, 2, 2
mesh = context.init_mesh(dp=dp, tp=tp, sp=sp, ep=ep)

def build(attn_fn):
    return models.MoETransformerLM(
        vocab=64, dim=8 * tp, n_layers=2, n_heads=2 * tp, n_kv_heads=tp,
        pos="rope", max_seq=8, n_experts=2 * ep, capacity_factor=4.0,
        attn_fn=attn_fn)

model = build(make_gspmd_ring_attn_fn(mesh, core="flash",
                                      block_q=4, block_k=4))
params = shard_params(model.init(jax.random.PRNGKey(0)),
                      model.param_specs(), mesh)
opt = optim.adamw(1e-3)
opt_state = opt.init(params)

def make_loss(m):
    def loss_fn(p, batch):
        x, y = batch
        logits, aux = m.apply(p, x)
        return cross_entropy_per_example(logits, y).mean() + 0.01 * aux, {}
    return loss_fn

step = make_spmd_train_step(make_loss(model), opt)
rng = np.random.default_rng(0)
toks = rng.integers(0, 64, (2 * dp, 8)).astype(np.int32)
batch = shard_batch_spec((toks, toks), mesh, P("dp", "sp"))
out = step(params, opt_state, batch)
jax.block_until_ready(out.loss)

# oracle: dense attention, unsharded params, one device
oracle_model = build(None)
p_full = model.init(jax.random.PRNGKey(0))
oracle = float(make_loss(oracle_model)(p_full, (toks, toks))[0])

# striped arm on the SAME 4-axis mesh: data-level striping (tokens,
# targets, positions) + the load-balanced causal ring, same oracle
from distributed_pytorch_tpu.parallel import stripe_tokens
from distributed_pytorch_tpu.parallel.spmd import (
    make_gspmd_striped_ring_attn_fn)
m_striped = build(make_gspmd_striped_ring_attn_fn(mesh, block_q=4,
                                                  block_k=4))
pos_st = stripe_tokens(jnp.arange(8), sp, axis=0)
x_st = np.asarray(stripe_tokens(jnp.asarray(toks), sp, axis=1))

def striped_loss_fn(p, batch):
    x, y = batch
    logits, aux = m_striped.apply(p, x, positions=pos_st)
    return cross_entropy_per_example(logits, y).mean() + 0.01 * aux, {}

step_st = make_spmd_train_step(striped_loss_fn, opt, donate=False)
params_st = shard_params(model.init(jax.random.PRNGKey(0)),
                         model.param_specs(), mesh)
batch_st = shard_batch_spec((x_st, x_st), mesh, P("dp", "sp"))
out_st = step_st(params_st, opt.init(params_st), batch_st)
jax.block_until_ready(out_st.loss)

print(json.dumps({"loss": float(out.loss), "oracle": oracle,
                  "loss_striped": float(out_st.loss),
                  "n_devices": jax.device_count()}))
"""


@pytest.mark.slow
def test_dp_tp_sp_ep_one_mesh_16dev_matches_oracle():
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "DPX_CPU_DEVICES": "16"}
    out = subprocess.run([sys.executable, "-c", _CODE],
                         capture_output=True, text=True, timeout=900,
                         env=env)
    assert out.returncode == 0, (out.stderr or out.stdout)[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["n_devices"] == 16
    np.testing.assert_allclose(rec["loss"], rec["oracle"],
                               rtol=5e-4, atol=5e-4)
    # the striped (load-balanced) ring on the same 4-axis mesh hits the
    # same oracle: striping is layout, not math
    np.testing.assert_allclose(rec["loss_striped"], rec["oracle"],
                               rtol=5e-4, atol=5e-4)
