"""The MoE layer under a mesh computes what it computes on one device:
the launch layer's ``expert_map`` runs the dropless routed part per
device (tokens over the batch axes, experts over ``model``) and sums the
parts. Runs in a child process with 8 host devices (a 2 x 4 mesh),
since the device count is fixed when JAX starts."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

CHILD = r'''
import json, sys, types
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.launch.mesh import make_debug_mesh
from repro.launch.sharding import (ShardingPolicy, batch_shardings,
                                   param_shardings, run_config_for)
from repro.models.transformer import RunConfig, init_params, loss_fn

mode = sys.argv[1]
cfg = get_config("deepseek-v2-lite-reduced")
base = RunConfig(chunk_q=32, chunk_kv=32, loss_chunk=64,
                 param_dtype=jnp.float32, cache_dtype=jnp.float32)
params = init_params(cfg, jax.random.key(0), base)
tokens = jax.random.randint(jax.random.key(1), (4, 64), 0, cfg.vocab_size)
batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}


def step(rc):
    return jax.value_and_grad(lambda p, b: loss_fn(cfg, rc, p, b),
                              has_aux=True)


(l1, m1), g1 = jax.jit(step(base))(params, batch)
mesh = make_debug_mesh(data=2, model=4)
policy = ShardingPolicy(mode=mode, fsdp=True)
rc = run_config_for(cfg, types.SimpleNamespace(global_batch=4), mesh,
                    base=base, policy=policy)
p_sh = param_shardings(cfg, params, mesh, policy)
b_sh = batch_shardings(mesh, False, 4, policy)
(l8, m8), g8 = jax.jit(step(rc), in_shardings=(p_sh, b_sh))(params, batch)
grad_gap = max(float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-12))
               for a, b in zip(jax.tree.leaves(g8), jax.tree.leaves(g1)))
print(json.dumps({"loss": [float(l1), float(l8)],
                  "aux": [float(m1["aux"]), float(m8["aux"])],
                  "rows": [int(m1["moe_rows"]), int(m8["moe_rows"])],
                  "max_rows": [int(m1["moe_max_rows"]),
                               int(m8["moe_max_rows"])],
                  "grad_gap": grad_gap,
                  "moe_layers": sum(l.mlp.kind == "moe"
                                    for l in cfg.layer_specs())}))
'''


@pytest.mark.parametrize("mode", ["tp_fsdp", "dp_zero1"])
def test_moe_under_a_mesh_equals_one_device(mode):
    import json
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", CHILD, mode], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    one, mesh = got["loss"]
    assert abs(mesh - one) <= 1e-5 * abs(one), got
    assert abs(got["aux"][1] - got["aux"][0]) <= 1e-5 * got["aux"][0], got
    # every expert held: each MoE layer computes all 4 x 64 x top-2 rows
    assert got["rows"][0] == got["rows"][1] == got["moe_layers"] * 512, got
    assert got["max_rows"][0] == got["max_rows"][1], got
    assert got["grad_gap"] < 1e-4, got
