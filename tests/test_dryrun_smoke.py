"""Integration: sharded lower+compile on a small host-device mesh.

Full production meshes (256/512 devices) are exercised by launch/dryrun.py;
here a subprocess gets 8 host devices and verifies the same code path
(shardings, mesh context, roofline extraction) end to end on reduced
configs.  Subprocess because the device count must be set before jax init.
"""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys
import jax, jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
from repro.configs import get_smoke_config
from repro.launch import sharding as shr
from repro.launch.dryrun import collective_stats, _cost_record
from repro.models.model import Model
from repro.models.shard_ctx import set_mesh_context
from repro.training.optim import OptimConfig, adamw_init
from repro.training.train import make_train_step

arch = sys.argv[1]
mesh = jax.make_mesh((2, 4), ("data", "model"), devices=jax.devices(),
                     axis_types=(AxisType.Auto,) * 2)
set_mesh_context(mesh, ("data",))
cfg = get_smoke_config(arch)
model = Model(cfg)
params = model.param_shapes()
p_sh = shr.param_shardings(cfg, params, mesh, fsdp=True)
batch = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32)}
if cfg.arch_type == "audio":
    batch = {"frame_embeds": jax.ShapeDtypeStruct((8, 64, cfg.d_model), jnp.bfloat16),
             "labels": jax.ShapeDtypeStruct((8, 64), jnp.int32)}
elif cfg.arch_type == "vlm":
    batch["patch_embeds"] = jax.ShapeDtypeStruct(
        (8, cfg.n_frontend_tokens, cfg.d_model), jnp.bfloat16)
b_sh = shr.batch_shardings(cfg, batch, mesh)
opt = jax.eval_shape(adamw_init, params)
opt_sh = shr.opt_shardings(p_sh, mesh)
step = make_train_step(model, OptimConfig())
rep = NamedSharding(mesh, P())
fn = jax.jit(step, in_shardings=(p_sh, opt_sh, b_sh),
             out_shardings=(p_sh, opt_sh, {"loss": rep, "grad_norm": rep, "lr": rep}))
compiled = fn.lower(params, opt, batch).compile()
rec = _cost_record(compiled)
assert rec["flops"] > 0
print(json.dumps({"arch": arch, "flops": rec["flops"],
                  "coll_counts": rec["coll_counts"]}))
"""


@pytest.mark.parametrize("arch", ["yi-9b", "deepseek-moe-16b", "mamba2-780m",
                                  "recurrentgemma-2b", "hubert-xlarge"])
def test_sharded_train_step_compiles(arch):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", SCRIPT, arch],
                         capture_output=True, text=True, env=env,
                         timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["flops"] > 0
