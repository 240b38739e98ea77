"""DeepSeek-V2 at a small size on the CPU, with seeded random weights,
against the plain reference (reference/deepseek_v2.py): latent attention
without a query low-rank under YaRN rope, the held-expert layer, the
expert share against the uncut layer, a whole cell through the harness,
and the cost module against the program's shapes."""
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_tiny_dsv2 as tiny
from benchmarks.chip import check, spec, weights

REF = spec.reference("deepseek_v2")


def _close(got, want, tol):
    got, want = np.asarray(got), np.asarray(want)
    assert np.max(np.abs(got - want)) <= tol * np.max(np.abs(want)), \
        np.max(np.abs(got - want)) / np.max(np.abs(want))


def _arch(cfg):
    return spec.arch("deepseek_v2").arch_config(dict(cfg, name="tiny"))


def _block(cfg, stage, key=7):
    """One layer's parameters of the tiny model, made by weights.py."""
    from repro.models.transformer import RunConfig, init_params
    arch = _arch(cfg)
    shapes = jax.eval_shape(lambda: init_params(
        arch, jax.random.key(0), RunConfig(param_dtype=jnp.float32)))
    lp = weights.make_params(shapes, key)["stages"][stage]["L0"]
    if stage:
        lp = jax.tree.map(lambda t: t[0], lp)
    return arch, lp


# -- (a) latent attention ----------------------------------------------------
def test_yarn_rope_constants():
    from repro.configs import get_config
    from repro.models import attention as A
    from repro.models.layers import rope_freqs
    (layer,) = get_config("deepseek-v2-lite").stages[1].block
    a = layer.attn
    cfg = tiny.tiny_config()
    cfg.update(qk_rope_head_dim=64, qk_nope_head_dim=128)
    freqs = np.asarray(rope_freqs(64, a.rope_theta, a.rope_scaling))
    ref = REF.yarn_inv_freq(cfg)
    np.testing.assert_allclose(freqs, ref, rtol=1e-6)
    base = 10_000.0 ** (-np.arange(0, 64, 2) / 64)
    # low = 10, high = 23: dims below 10 keep base, from 23 on base / 40
    np.testing.assert_allclose(freqs[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(freqs[23:], base[23:] / 40, rtol=1e-6)
    assert not np.allclose(freqs[11:23], base[11:23], rtol=1e-3)
    assert abs(A._mla_scale(a) - 0.114721) < 1e-6


def test_mla_forward_matches_reference():
    from repro.models import attention as A
    cfg = tiny.tiny_config()
    arch, lp = _block(cfg, 1)
    spec_ = arch.stages[1].block[0].attn
    assert spec_.mla.q_lora_rank is None and "wq" in lp["mixer"]
    x = jax.random.normal(jax.random.key(1), (2, 48, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, _ = A.mla_forward(lp["mixer"], x, spec_,
                               positions=jnp.arange(48), impl="chunked",
                               chunk_q=16, chunk_kv=16)
        want = REF._attention(lp["mixer"], x, cfg)
    _close(got, want, 2e-5)


@pytest.mark.parametrize("absorb", [True, False])
def test_mla_decode_matches_forward(absorb):
    from repro.models import attention as A
    cfg = tiny.tiny_config()
    arch, lp = _block(cfg, 1)
    spec_ = arch.stages[1].block[0].attn
    s = 24
    x = jax.random.normal(jax.random.key(2), (2, s + 1, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        full, _ = A.mla_forward(lp["mixer"], x, spec_,
                                positions=jnp.arange(s + 1), impl="naive",
                                chunk_q=8, chunk_kv=8)
        cache = {"c_kv": jnp.zeros((2, s + 4, 16)),
                 "k_rope": jnp.zeros((2, s + 4, 8))}
        _, cache = A.mla_forward(lp["mixer"], x[:, :s], spec_,
                                 positions=jnp.arange(s), impl="naive",
                                 chunk_q=8, chunk_kv=8, cache=cache)
        step, _ = A.mla_decode(lp["mixer"], x[:, s:], spec_,
                               pos=jnp.asarray(s, jnp.int32), cache=cache,
                               absorb=absorb)
    _close(step[:, 0], full[:, s], 2e-5)


# -- (b) the held-expert layer -----------------------------------------------
def _moe_setup(n_experts=16, held=4, top_k=4, first=0, skew=0.0, key=3):
    from repro.configs.base import MoESpec
    from repro.models import moe as M
    spec_ = MoESpec(n_experts=n_experts, top_k=top_k, d_expert=32,
                    n_shared=1, norm_topk=False, held_first=first, held=held)
    params = M.init_moe(jax.random.key(key), 64, spec_, "swiglu",
                        jnp.float32)
    x = jax.random.normal(jax.random.key(key + 1), (2, 40, 64), jnp.float32)
    if skew:  # every token's top-k among the held experts
        x = x + 1.0
        params["router"] = params["router"].at[:, first:first + held].add(
            skew)
    cfg = {"published": {"n_routed_experts": n_experts},
           "num_experts_per_tok": top_k, "norm_topk_prob": False,
           "n_routed_experts": held}
    return spec_, params, x, cfg


def _ref_moe(p, x, cfg):
    """The reference's layer, and its count of held assignments."""
    with jax.default_matmul_precision("highest"):
        y, aux = REF._moe(p, x, cfg)
        _, (_, top_i) = REF.routing(p, x, cfg)
    return y, aux, int(jnp.sum(top_i < cfg["n_routed_experts"]))


@pytest.mark.parametrize("skew", [0.0, 0.5])
def test_held_moe_matches_reference(skew):
    from repro.models import moe as M
    spec_, params, x, cfg = _moe_setup(skew=skew)
    with jax.default_matmul_precision("highest"):
        y, stats = jax.jit(partial(M.apply_held_moe, spec=spec_,
                                   act="swiglu"))(params, x)
    want, aux, rows = _ref_moe(params, x, cfg)
    _close(y, want, 1e-5)
    np.testing.assert_allclose(float(stats["aux"]), float(aux.mean()),
                               rtol=1e-5)
    assert int(stats["moe_rows"]) == rows
    if skew:  # nothing dropped: all of the 2 passes' rows computed
        assert rows == x.shape[0] * x.shape[1] * spec_.top_k
        assert M._passes(spec_.n_experts, spec_.n_held, rows)[0] == 2


def test_held_moe_gradients_match_reference():
    from repro.models import moe as M
    spec_, params, x, cfg = _moe_setup(skew=0.25)
    w = jax.random.normal(jax.random.key(9), x.shape, jnp.float32)

    def prog(p, x):
        y, stats = M.apply_held_moe(p, x, spec_, "swiglu")
        return jnp.sum(y * w) + stats["aux"]

    def ref(p, x):
        y, aux = REF._moe(p, x, cfg)
        return jnp.sum(y * w) + aux.mean()

    with jax.default_matmul_precision("highest"):
        gp = jax.grad(prog, argnums=(0, 1))(params, x)
        gr = jax.grad(ref, argnums=(0, 1))(params, x)
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        _close(a, b, 1e-5)


# -- (c) the share against the uncut layer -----------------------------------
@pytest.mark.parametrize("shares", [2, 4])
def test_shares_add_up_to_the_uncut_layer(shares):
    import dataclasses

    from repro.models import moe as M
    full, params, x, cfg = _moe_setup(held=16)
    full = dataclasses.replace(full, held=0)
    with jax.default_matmul_precision("highest"):
        y_full, st_full = M.apply_held_moe(params, x, full, "swiglu")
        want, _, _ = _ref_moe(params, x, dict(cfg, n_routed_experts=16))
        n = 16 // shares
        total, rows = 0.0, 0
        shared = M.apply_mlp(params["shared"], x, "swiglu")
        for i in range(shares):
            part = dataclasses.replace(full, held_first=i * n, held=n)
            p = {k: (v[i * n:(i + 1) * n] if k.startswith("w_") else v)
                 for k, v in params.items()}
            y, st = M.apply_held_moe(p, x, part, "swiglu")
            total = total + y - shared
            rows += int(st["moe_rows"])
            assert float(st["aux"]) == pytest.approx(float(st_full["aux"]),
                                                     rel=1e-6)
    _close(y_full, want, 1e-5)
    _close(total + shared, y_full, 1e-5)
    assert rows == int(st_full["moe_rows"]) == x.shape[0] * x.shape[1] * 4


# -- (d) a whole cell through the harness ------------------------------------
@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("chipbench-dsv2"))


def test_norm_gains_start_at_one(bench):
    from repro.models.transformer import RunConfig, init_params
    arch = _arch(bench.config(tiny.CONFIG))
    shapes = jax.eval_shape(lambda: init_params(
        arch, jax.random.key(0), RunConfig(param_dtype=jnp.float32)))
    flat, _ = jax.tree_util.tree_flatten_with_path(
        weights.make_params(shapes, 11))
    norms = {weights.leaf_name(p): x for p, x in flat
             if "norm" in weights.leaf_name(p) or "/ln" in
             weights.leaf_name(p)}
    assert len(norms) == 7  # ln1, ln2, kv_a_norm of each stage; final
    assert all(n.endswith("/scale") for n in norms)
    assert all(bool(jnp.all(x == 1)) for x in norms.values())


def test_cell_is_correct(bench):
    result, checks = tiny.run(bench, tiny.CELL)
    assert result["correct"], checks
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_widths_that_differ_from_the_registry_are_refused():
    cfg = dict(tiny.tiny_config(), name="tiny", moe_intermediate_size=48)
    with pytest.raises(ValueError, match="moe_intermediate_size"):
        spec.arch("deepseek_v2").arch_config(cfg)
    cfg = tiny.tiny_config()
    cfg["published"] = dict(cfg["published"], n_routed_experts=8)
    with pytest.raises(ValueError, match="n_routed_experts"):
        spec.arch("deepseek_v2").arch_config(dict(cfg, name="tiny"))


@pytest.mark.parametrize("fault", ["half_batch", "bfloat16_control"])
def test_faults_are_not_correct(bench, fault):
    """The reference fed half of each batch, and the reference computed
    in bfloat16, each in the program's place: both fail a limit that the
    program keeps."""
    from benchmarks.chip.job import Job
    cfg = bench.config(tiny.CONFIG)
    job = Job(cfg, bench.traffic(tiny.TRAFFIC), 5,
              run_config=tiny.RUN_CONFIG)
    job.build()
    job.run_first_steps(3)
    start = partial(weights.make_params, job.shapes, 5)
    batches = [job._batch(i) for i in range(3)]
    ref = REF.train_steps(cfg, start(), batches)
    if fault == "half_batch":
        bad = REF.train_steps(cfg, start(), [
            {k: v[:len(v) // 2] for k, v in b.items()} for b in batches])
    else:
        bad = REF.train_steps(cfg, start(), batches, dtype=jnp.bfloat16)
    limits = cfg["limits"]
    prog = check.training_numbers(job.first_steps, ref)
    faulted = check.training_numbers(bad, ref)
    assert all(v <= limits[k] for k, v in prog.items()), prog
    assert any(v > limits[k] for k, v in faulted.items()), faulted


# -- (e) the cost module -----------------------------------------------------
def test_deepseek_v2_step_flops_by_hand():
    cfg = tiny.tiny_config()
    cost = spec.cost("deepseek_v2_step")
    # d 64, 4 heads, nope 8, rope 8, v 8, kv rank 16: wq 64*64, kv_a
    # 64*24, kv_b 16*64, wo 32*64
    attn = 4096 + 1536 + 1024 + 2048
    expert = 3 * 64 * 32
    # 3 layers; 1 dense (3*64*64); 2 MoE: 1 shared, router 64*4, 2*2/4
    # routed; head 64*512
    weights_ = 3 * attn + 3 * 64 * 64 + 2 * (expert + 256 + expert) \
        + 64 * 512
    scores = 3 * 3 * 2 * 4 * 24 * 64 // 2
    assert cost.attention_weights(cfg) == attn
    assert cost.matmul_weights(cfg) == weights_
    assert cost.flops_per_token(cfg) == 6 * weights_ + scores
    assert cost.expert_flops_per_step(cfg, 128) == 9 * 2 * (128 * 2 * 1) \
        * 64 * 32
    published = spec.Bench().config(tiny.CONFIG)
    assert math.isclose(cost.flops_per_token(published) / 1e9, 1.862271,
                        rel_tol=1e-6)


MS = 1_000_000


def _traced_run(scoped, kernels):
    """A run whose 100 ms window (from 1,000 ms on) holds two step
    runs, the ops ``scoped`` [(scope path, start ms, end ms)] and the
    device op events ``kernels`` [(event name, start ms, end ms)]."""
    import types

    from benchmarks.chip import spans, trace
    w = (1000 * MS, 1100 * MS)
    pt = spans.ProgramTrace(window=w, program_spans=[], scoped_ops={0: [
        (p, s * MS, e * MS) for p, s, e in scoped]})
    t = trace.Trace(window=w, device_ops={0: [
        (n, s * MS, e * MS) for n, s, e in kernels]},
        modules=[("jit_step_fn(1)", w[0], w[0] + 40 * MS),
                 ("jit_step_fn(2)", w[0] + 50 * MS, w[0] + 90 * MS)])
    return types.SimpleNamespace(
        trace=t, program_trace=pt, cfg=spec.Bench().config(tiny.CONFIG),
        job=types.SimpleNamespace(tokens_per_step=16384),
        peak={"bf16_flops_per_s": 197e12})


# the expert layer of one step: routing, then the held experts' products
# (the ragged dots as XLA's kernels, which carry no scope, and a scoped
# silu), and the shared expert; latent attention before it
MOE_STEP = (
    [("jit(step_fn)/jvp()/while/body/mla/dot_general", 1000, 1010),
     ("jit(step_fn)/jvp()/while/body/moe/sort", 1010, 1012),
     ("jit(step_fn)/jvp()/while/body/moe/cond", 1012, 1030),
     ("jit(step_fn)/jvp()/while/body/moe/cond/branch_1_fun/moe_experts/"
      "jit(silu)/mul", 1020, 1022),
     ("jit(step_fn)/jvp()/while/body/moe/dot_general", 1030, 1034)],
    [("%ragged-dot-metadata.3 = (s32[9]) custom-call()", 1012, 1013),
     ("%ragged-dot-none.9 = f32[24576,1408] custom-call()", 1013, 1020),
     ("%ragged-dot-none.7 = f32[24576,2048] custom-call()", 1022, 1030),
     ("%fusion.2 = f32[8] fusion()", 1034, 1040)])


def test_readers_add_the_grouped_kernels_to_their_scope():
    run = _traced_run(*MOE_STEP)
    secs = 0.018 / 2  # metadata 1 + products 7 + 8 + silu 2 ms, 2 runs
    assert spec.metric_reader("train_step.moe_s")(run) == pytest.approx(
        0.024 / 2)  # 1010-1034; the cond holds the kernels
    assert spec.metric_reader("train_step.mla_s")(run) == pytest.approx(
        0.005)
    flops = spec.cost("deepseek_v2_step").expert_flops_per_step(
        run.cfg, 16384)
    assert spec.metric_reader("moe_experts_roofline")(run) == \
        pytest.approx(100 * flops / secs / 197e12)


@pytest.mark.parametrize("metric", ["train_step.moe_s", "train_step.mla_s",
                                    "moe_experts_roofline"])
def test_readers_read_zero_from_a_program_without_them(metric):
    """A program that names neither scope and runs no ragged dot (the
    one before this model, in a traced run) reads 0, and an untraced
    run reads nothing."""
    import types
    run = _traced_run([("jit(step_fn)/jvp()/wkv/while", 1000, 1040)],
                      [("%fusion.1 = f32[8] fusion()", 1000, 1040)])
    assert spec.metric_reader(metric)(run) == 0.0
    assert spec.metric_reader(metric)(types.SimpleNamespace(
        trace=None)) is None


def test_deepseek_v2_weights_match_the_program():
    from repro.models.transformer import RunConfig, init_params
    cfg = tiny.tiny_config()
    shapes = jax.eval_shape(lambda: init_params(
        _arch(cfg), jax.random.key(0), RunConfig()))
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    size = {weights.leaf_name(p): x.size for p, x in flat}
    cost = spec.cost("deepseek_v2_step")
    attn = sum(v for k, v in size.items() if k.startswith("/stages/0/")
               and "/mixer/" in k and not k.endswith("scale"))
    assert attn == cost.attention_weights(cfg)
    experts = sum(v for k, v in size.items() if k.startswith("/stages/1/")
                  and k.rsplit("/", 2)[1] == "mlp"
                  and k.endswith(("w_gate", "w_up", "w_down")))
    held = cfg["n_routed_experts"] * 2  # layers
    assert experts == held * cost.expert_weights(cfg)
    dense = sum(v for k, v in size.items() if k.startswith("/stages/0/")
                and "/mlp/" in k)
    shared = sum(v for k, v in size.items() if "/shared/" in k)
    router = sum(v for k, v in size.items() if k.endswith("router"))
    n_moe = 2
    assert cost.matmul_weights(cfg) == (
        3 * attn + dense + shared + router + size["/lm_head"]
        + n_moe * cost.routed_rows_per_token(cfg) * cost.expert_weights(cfg))
