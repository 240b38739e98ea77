"""The benchmark's operation and byte counts against the shapes they
stand for."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

import chipbench_tiny as tiny
from benchmarks.chip import spec

MATMUL_WEIGHTS = ("wr", "wk", "wv", "wg", "wo", "mix_w1", "mix_w2", "dw1",
                  "dw2", "ck", "cv", "cr", "lm_head")


def _pallas_calls(jaxpr):
    out = []
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            out.append(e)
        for v in e.params.values():
            inner = getattr(v, "jaxpr", None)
            if inner is not None and hasattr(inner, "eqns"):
                out += _pallas_calls(inner)
    return out


def test_delta_mask_bytes_match_the_kernels_operands():
    from repro.kernels.delta_encode import delta_mask
    block, bpt = 4096, 8
    scanned = block * bpt * 3
    words = jax.ShapeDtypeStruct((scanned // 4,), jnp.uint32)
    jaxpr = jax.make_jaxpr(partial(delta_mask, block=block, bpt=bpt,
                                   interpret=True))(words, words)
    (call,) = _pallas_calls(jaxpr.jaxpr)
    moved = sum(np.prod(v.aval.shape) * v.aval.dtype.itemsize
                for v in list(call.invars) + list(call.outvars))
    assert spec.cost("delta_mask").bytes_moved(scanned, block) == moved


def test_rwkv6_step_flops_by_hand():
    cfg = dict(tiny.TINY, num_hidden_layers=1)
    # d=64, ffn 64, mix lora 4, decay lora 8, vocab 512, heads of 8:
    # 5*64*64 + 2*5*4*64 + 2*8*64 + 2*64*64 + 64*64 + 64*512 weights
    weights = 20480 + 2560 + 1024 + 8192 + 4096 + 32768
    wkv = 8 * 21 * 8 * 8  # 8 heads, 21 n^2 operations each
    cost = spec.cost("rwkv6_step")
    assert cost.matmul_weights(cfg) == weights
    assert cost.flops_per_token(cfg) == 6 * weights + wkv


def test_rwkv6_matmul_weights_match_the_program():
    from repro.models.transformer import RunConfig, init_params
    cfg = dict(tiny.TINY, name="tiny", num_hidden_layers=1,
               layer_norm_epsilon=1e-5, tie_word_embeddings=False)
    shapes = jax.eval_shape(lambda: init_params(
        spec.arch("rwkv6").arch_config(cfg), jax.random.key(0), RunConfig()))
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    counted = sum(x.size for p, x in flat
                  if str(getattr(p[-1], "key", "")) in MATMUL_WEIGHTS)
    assert spec.cost("rwkv6_step").matmul_weights(cfg) == counted
