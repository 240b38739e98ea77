"""Compile-only checks for a described v5e chip (no chip attached): the
checkpoint path's ``delta_mask`` kernel at the default 64 KB block, for a
small leaf, the chip smoke's largest leaf (its 16,384 x 2048 f32
embedding share, 128 MiB) and rwkv6-1.6b's whole 65,536 x 2048 f32
embedding (512 MiB), each as uint32 words. The TPU compiler
refuses what interpret mode accepts (the (8, 128) block rule, comparisons
the chip cannot do), and a layout that pads the words would need many
times the leaf in temporary memory.

And rwkv6-1.6b's time mix, forward and backward, at the benchmark's batch
(16 x 2048 tokens, 32 heads of 64): its memory plan against that of the
wkv scan's earlier form, which built the (B, L, L, H, dk) pairwise decays
of each 64-token chunk.

And the DeepSeek-V2-Lite cell's whole train step, which the compiler
refuses if it does not fit the chip's memory."""
import os
from functools import partial

import pytest

import jax
import jax.numpy as jnp

from repro.ckpt.checkpoint import CheckpointConfig
from repro.kernels.delta_encode import delta_mask
from repro.models import ssm as S

BLOCK = CheckpointConfig().delta_block  # 64 KB
LEAF_BYTES = {"small": 8 * BLOCK, "smoke_embedding": 16_384 * 2048 * 4,
              "embedding": 65_536 * 2048 * 4}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.mark.parametrize("leaf", sorted(LEAF_BYTES))
def test_delta_mask_compiles_for_v5e(leaf, one_chip, no_compile_cache):
    words = jax.ShapeDtypeStruct((LEAF_BYTES[leaf] // 4,), jnp.uint32,
                                 sharding=one_chip)
    compiled = delta_mask.lower(words, words, block=BLOCK,
                                interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def _pairwise_chunk(r, k, v, logw, u, state0):
    """The earlier wkv chunk. r/k/v/logw:(B,L,H,dk|dv), state0:(B,H,dk,dv)."""
    lwc = jnp.cumsum(logw, axis=1)
    ex = lwc - logw
    y_inter = jnp.einsum("blhd,bhdv->blhv", r * jnp.exp(ex), state0)
    diff = ex[:, :, None] - lwc[:, None, :]  # (B,Lt,Ls,H,dk)
    tri = jnp.tril(jnp.ones((r.shape[1], r.shape[1]), jnp.float32), k=-1)
    pair = jnp.exp(jnp.minimum(diff, 0.0)) * tri[None, :, :, None, None]
    amat = jnp.einsum("bthd,bshd,btshd->bhts", r, k, pair)
    diag = jnp.einsum("bthd,hd,bthd->bth", r, u, k)
    y_intra = jnp.einsum("bhts,bshv->bthv", amat, v) + diag[..., None] * v
    k_dec = k * jnp.exp(lwc[:, -1][:, None] - lwc)
    s_new = jnp.exp(lwc[:, -1])[..., None] * state0 + jnp.einsum(
        "bshd,bshv->bhdv", k_dec, v)
    return y_inter + y_intra, s_new


def _pairwise_wkv(r, k, v, logw, u, state0, chunk):
    """The earlier scan: (nc, B, L, H, d) chunks of ``_pairwise_chunk``."""
    b, s, h, d = r.shape
    nc = s // chunk

    def split(t):
        return t.reshape(b, nc, chunk, h, d).transpose(1, 0, 2, 3, 4)

    @partial(jax.checkpoint, prevent_cse=False)
    def body(st, xs):
        y, st = _pairwise_chunk(*xs, u, st)
        return st, y

    state, ys = jax.lax.scan(body, state0,
                             tuple(split(t) for t in (r, k, v, logw)))
    return ys.transpose(1, 0, 2, 3, 4).reshape(b, s, h, d), state


def test_rwkv_time_mix_plan_within_pairwise_form(one_chip, no_compile_cache,
                                                  monkeypatch):
    from repro.configs import get_config
    (stage,) = get_config("rwkv6-1.6b").stages
    spec = stage.block[0].rwkv
    d_model = get_config("rwkv6-1.6b").d_model
    assert (d_model // spec.head_dim, spec.head_dim) == (32, 64)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: S.init_rwkv(jax.random.key(0), d_model, spec,
                                           jnp.float32)))
    x = jax.ShapeDtypeStruct((16, 2048, d_model), jnp.float32,
                             sharding=one_chip)

    def loss(p, x):
        out, _ = S.rwkv_time_mix(p, x, spec, chunk=64)
        return jnp.sum(out ** 2)

    def temp_bytes():  # traced anew: reads S._wkv as it stands
        compiled = jax.jit(jax.value_and_grad(loss)).lower(params, x).compile()
        return compiled.memory_analysis().temp_size_in_bytes

    sub_blocks = temp_bytes()
    monkeypatch.setattr(S, "_wkv", _pairwise_wkv)
    assert sub_blocks < temp_bytes()


def test_dsv2lite_cell_step_fits_a_v5e(one_chip, no_compile_cache):
    """The ``dsv2lite-5l.steady`` cell's train step (DeepSeek-V2-Lite, 1
    dense + 4 MoE layers holding 8 of 64 experts, vocabulary 12,800, f32
    state and AdamW moments) at its traffic's 4 x 4096 tokens. The TPU
    compiler refuses a program whose buffers, the state among them,
    exceed the chip's memory, so compiling is the check; the state is the
    6.42 GB the configuration states."""
    import sys
    from pathlib import Path
    root = str(Path(__file__).resolve().parents[1])
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmarks.chip import spec
    from repro.launch.train import make_train_step
    from repro.models.transformer import RunConfig, init_params
    from repro.optim.adamw import AdamWConfig, adamw_init
    bench = spec.Bench()
    cell = bench.workload("dsv2lite-5l.steady")
    cfg = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    arch = spec.arch(cfg["arch"]).arch_config(cfg)
    rc = RunConfig(param_dtype=jnp.float32, cache_dtype=jnp.float32)
    params = jax.eval_shape(lambda: init_params(arch, jax.random.key(0), rc))
    on_chip = partial(jax.tree.map, lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip))
    batch = {k: jax.ShapeDtypeStruct((traffic["batch"], traffic["seq"]),
                                     jnp.int32, sharding=one_chip)
             for k in ("tokens", "labels")}
    step = make_train_step(arch, rc, AdamWConfig(**cfg["optimizer"]))
    compiled = step.lower(on_chip(params), on_chip(jax.eval_shape(
        adamw_init, params)), batch).compile()
    mem = compiled.memory_analysis()
    n_params = sum(x.size for x in jax.tree.leaves(params))
    assert n_params == 535_060_992
    assert mem.argument_size_in_bytes - 12 * n_params < 1 << 20
    assert "ragged" in compiled.as_text()
