"""Data pipeline determinism/checkpointing + optimizer + compression."""
import numpy as np

import jax
import jax.numpy as jnp

from repro.data import TokenPipeline
from repro.optim import (AdamWConfig, CompressionConfig, adamw_init,
                         adamw_update, compress_grads, decompress_grads,
                         init_error_state)


def _pipe(**kw):
    kw.setdefault("vocab_size", 100)
    kw.setdefault("seq_len", 16)
    kw.setdefault("global_batch", 8)
    kw.setdefault("prefetch", 0)
    return TokenPipeline(**kw)


def test_pipeline_determinism():
    p1, p2 = _pipe(seed=3), _pipe(seed=3)
    for _ in range(3):
        b1, b2 = p1.next(), p2.next()
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    p3 = _pipe(seed=4)
    assert not np.array_equal(p3.next()["tokens"], _pipe(seed=3).next()[
        "tokens"])


def test_pipeline_steps_do_not_overlap():
    p = _pipe(seed=7, vocab_size=65_536)
    b0, b1 = (p.batch_at(i)["tokens"].ravel() for i in (0, 1))
    n = len(b0)
    assert not any(np.array_equal(b0[k:], b1[:n - k]) for k in range(n - 8))


def test_pipeline_snapshot_restore():
    p = _pipe(seed=1)
    p.next()
    p.next()
    snap = p.snapshot()
    b3 = p.next()
    p2 = _pipe(seed=1)
    p2.restore(snap)
    np.testing.assert_array_equal(p2.next()["tokens"], b3["tokens"])


def test_pipeline_shards_partition():
    full = _pipe(seed=9, n_shards=1, shard=0, global_batch=8)
    s0 = _pipe(seed=9, n_shards=2, shard=0, global_batch=8)
    s1 = _pipe(seed=9, n_shards=2, shard=1, global_batch=8)
    assert s0.local_batch == 4 and s1.local_batch == 4
    assert not np.array_equal(s0.next()["tokens"], s1.next()["tokens"])


def test_pipeline_elastic_reshard():
    p = _pipe(seed=2, n_shards=2, shard=0, global_batch=8)
    p.next()
    p.reshard(4, 1)
    assert p.local_batch == 2
    assert p.next()["tokens"].shape == (2, 16)


def test_adamw_optimizes_quadratic():
    params = {"w": jnp.asarray([4.0, -3.0])}
    opt = adamw_init(params)
    cfg = AdamWConfig(lr=0.2, weight_decay=0.0, warmup_steps=1)

    def loss(p):
        return jnp.sum(jnp.square(p["w"]))

    for _ in range(60):
        g = jax.grad(loss)(params)
        params, opt, _ = adamw_update(cfg, g, opt, params)
    assert float(loss(params)) < 0.05


def test_grad_clip():
    from repro.optim import clip_by_global_norm
    g = {"a": jnp.full((4,), 100.0)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert float(norm) > 1.0
    total = float(jnp.sqrt(jnp.sum(jnp.square(clipped["a"]))))
    assert abs(total - 1.0) < 1e-5


def test_compression_error_feedback_converges():
    """int8 compression with error feedback: mean dequantized grad over
    many steps converges to the true grad (unbiased prefix)."""
    rng = np.random.default_rng(0)
    g_true = {"w": jnp.asarray(rng.standard_normal(256).astype(np.float32))}
    err = init_error_state(g_true)
    cfg = CompressionConfig(enabled=True, block=64)
    acc = np.zeros(256, np.float32)
    n = 30
    for _ in range(n):
        wire, err = compress_grads(g_true, err, cfg)
        deq = decompress_grads(wire, g_true)
        acc += np.asarray(deq["w"]) / n
    np.testing.assert_allclose(acc, np.asarray(g_true["w"]), atol=2e-2)


def test_compression_wire_is_int8():
    g = {"w": jnp.ones((256,), jnp.float32)}
    wire, _ = compress_grads(g, init_error_state(g),
                             CompressionConfig(block=64))
    assert wire["q"]["w"].dtype == jnp.int8
