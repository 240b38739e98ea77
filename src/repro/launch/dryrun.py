import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# isort: split  — the two lines above MUST run before jax is imported.
import argparse
import dataclasses
import json
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, SHAPES, get_config, shape_applicable
from repro.launch.mesh import make_production_mesh, mesh_chips
from repro.launch.sharding import (batch_shardings, cache_shardings,
                                   choose_policy, opt_shardings,
                                   param_shardings, run_config_for)
from repro.models.transformer import (RunConfig, count_active_params,
                                      count_params, decode_step, init_cache,
                                      init_params, loss_fn, prefill)
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update
from repro import roofline

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


def _sds_tree(f, *args, **kw):
    return jax.eval_shape(partial(f, *args, **kw), jax.random.key(0)) \
        if f is init_params else jax.eval_shape(partial(f, *args, **kw))


def make_train_step(cfg, rc, opt_cfg=AdamWConfig()):
    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, rc, p, batch), has_aux=True)(params)
        params, opt_state, gnorm = adamw_update(opt_cfg, grads, opt_state,
                                                params)
        return params, opt_state, {"loss": loss, "gnorm": gnorm, **metrics}
    return train_step


def make_prefill_step(cfg, rc):
    def prefill_step(params, tokens, caches, frontend=None):
        return prefill(cfg, rc, params, tokens, caches, frontend=frontend)
    return prefill_step


def make_decode_step(cfg, rc):
    def serve_step(params, tokens, pos, caches):
        return decode_step(cfg, rc, params, tokens, pos, caches)
    return serve_step


def input_specs(cfg, shape):
    """ShapeDtypeStruct stand-ins for every model input (no allocation)."""
    b, s = shape.global_batch, shape.seq_len
    sds = jax.ShapeDtypeStruct
    if shape.kind == "train":
        batch = {"tokens": sds((b, s), jnp.int32),
                 "labels": sds((b, s), jnp.int32)}
        if cfg.n_frontend:
            batch["frontend_embeds"] = sds((b, cfg.n_frontend, cfg.d_model),
                                           jnp.bfloat16)
        return {"batch": batch}
    if shape.kind == "prefill":
        out = {"tokens": sds((b, s), jnp.int32)}
        if cfg.n_frontend:
            out["frontend"] = sds((b, cfg.n_frontend, cfg.d_model),
                                  jnp.bfloat16)
        return out
    return {"tokens": sds((b, 1), jnp.int32),
            "pos": sds((), jnp.int32)}  # decode


def lower_cell(cfg, shape, mesh, rc_base=None, policy=None,
               opt_cfg=AdamWConfig(), hlo_path=None):
    """Lower + compile one (arch x shape x mesh) cell. Returns record dict."""
    policy = policy or choose_policy(cfg, shape, mesh,
                                     model_axis=mesh.shape["model"])
    rc = run_config_for(cfg, shape, mesh, base=rc_base, policy=policy)
    if policy.mode == "tp_fsdp" and rc.head_pad == 1:
        # head-TP: pad head counts to the model-axis multiple (zero-padded
        # heads are numerically inert — see models/attention.init_attn)
        rc = dataclasses.replace(rc, head_pad=mesh.shape["model"])
    params_shape = _sds_tree(init_params, cfg, rc=rc)
    p_sh = param_shardings(cfg, params_shape, mesh, policy)
    specs = input_specs(cfg, shape)
    repl = NamedSharding(mesh, P())

    t0 = time.time()
    if shape.kind == "train":
        opt_shape = _sds_tree(adamw_init, params_shape)
        o_sh = opt_shardings(cfg, opt_shape, p_sh, mesh, policy)
        b_sh = batch_shardings(mesh, cfg.n_frontend > 0, shape.global_batch,
                               policy)
        b_sh = {k: b_sh[k] for k in specs["batch"]}
        step = make_train_step(cfg, rc, opt_cfg)
        jitted = jax.jit(step,
                         in_shardings=(p_sh, o_sh, b_sh),
                         out_shardings=(p_sh, o_sh, repl),
                         donate_argnums=(0, 1))
        lowered = jitted.lower(params_shape, opt_shape, specs["batch"])
    elif shape.kind == "prefill":
        max_len = shape.seq_len + cfg.n_frontend
        cache_shape = _sds_tree(init_cache, cfg, shape.global_batch, max_len,
                                rc)
        c_sh = cache_shardings(cache_shape, mesh, policy, shape.global_batch)
        b_sh = batch_shardings(mesh, cfg.n_frontend > 0, shape.global_batch,
                               policy)
        step = make_prefill_step(cfg, rc)
        args = [params_shape, specs["tokens"], cache_shape]
        in_sh = [p_sh, b_sh["tokens"], c_sh]
        if cfg.n_frontend:
            args.append(specs["frontend"])
            in_sh.append(b_sh["frontend_embeds"])
        jitted = jax.jit(step, in_shardings=tuple(in_sh),
                         out_shardings=(repl, c_sh), donate_argnums=(2,))
        lowered = jitted.lower(*args)
    else:  # decode
        max_len = shape.seq_len + cfg.n_frontend
        cache_shape = _sds_tree(init_cache, cfg, shape.global_batch, max_len,
                                rc)
        c_sh = cache_shardings(cache_shape, mesh, policy, shape.global_batch)
        b_sh = batch_shardings(mesh, False, shape.global_batch, policy)
        step = make_decode_step(cfg, rc)
        jitted = jax.jit(step,
                         in_shardings=(p_sh, b_sh["tokens"], repl, c_sh),
                         out_shardings=(repl, c_sh),
                         donate_argnums=(3,))
        lowered = jitted.lower(params_shape, specs["tokens"], specs["pos"],
                               cache_shape)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    if hlo_path:
        with open(hlo_path, "w") as f:
            f.write(hlo)
    chips = mesh_chips(mesh)
    n_active = count_active_params(cfg)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
    else:
        tokens = shape.global_batch * (shape.seq_len if shape.kind ==
                                       "prefill" else 1)
    mf = roofline.model_flops(n_active, shape.kind, tokens) / chips
    terms = roofline.roofline_terms(hlo, model_flops_per_chip=mf)
    record = {
        "arch": cfg.name,
        "shape": shape.name,
        "mesh": dict(mesh.shape),
        "chips": chips,
        "policy": dataclasses.asdict(policy),
        "rc": {k: str(v) for k, v in dataclasses.asdict(
            rc).items() if k != "shard"},
        "params_total": count_params(cfg),
        "params_active": n_active,
        "tokens_per_step": tokens,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_estimate_bytes": mem.argument_size_in_bytes
            + mem.temp_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes,
        },
        "xla_cost_analysis": {"flops": ca.get("flops"),
                              "bytes_accessed": ca.get("bytes accessed")},
        "roofline": terms,
    }
    return record


def run_cell(arch: str, shape_name: str, mesh_kind: str, rc_overrides=None,
             tag: str = "", fsdp=None, out_dir: str = RESULTS_DIR,
             dump_hlo: bool = False):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if not shape_applicable(cfg, shape):
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "skipped": "full-attention arch: long_500k not applicable "
                           "(see DESIGN.md)"}
    mesh = make_production_mesh(multi_pod=(mesh_kind == "pod2"))
    rc_base = RunConfig(**(rc_overrides or {}))
    policy = None
    if fsdp is not None:
        from repro.launch.sharding import ShardingPolicy
        policy = ShardingPolicy(mode="tp_fsdp", fsdp=fsdp,
                                shard_cache_seq=(shape_name == "long_500k"))
    os.makedirs(out_dir, exist_ok=True)
    stem = f"{arch}_{shape_name}_{mesh_kind}{('_' + tag) if tag else ''}"
    hlo_path = os.path.join(out_dir, stem + ".hlo.txt") if dump_hlo else None
    with mesh:
        rec = lower_cell(cfg, shape, mesh, rc_base=rc_base, policy=policy,
                         hlo_path=hlo_path)
    rec["mesh_kind"] = mesh_kind
    rec["tag"] = tag
    fname = stem + ".json"
    with open(os.path.join(out_dir, fname), "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default="all", help="arch id or 'all'")
    ap.add_argument("--shape", default="all", help="shape name or 'all'")
    ap.add_argument("--mesh", default="both", choices=["pod1", "pod2",
                                                       "both"])
    ap.add_argument("--tag", default="")
    ap.add_argument("--attn-impl", default=None)
    ap.add_argument("--chunk-kv", type=int, default=None)
    ap.add_argument("--chunk-q", type=int, default=None)
    ap.add_argument("--mamba-chunk", type=int, default=None)
    ap.add_argument("--rwkv-chunk", type=int, default=None)
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--no-mla-absorb", action="store_true")
    ap.add_argument("--fsdp", default=None, choices=[None, "on", "off"])
    ap.add_argument("--out-dir", default=RESULTS_DIR)
    ap.add_argument("--dump-hlo", action="store_true")
    args = ap.parse_args()

    rc_over = {}
    for k, v in [("attn_impl", args.attn_impl), ("chunk_kv", args.chunk_kv),
                 ("chunk_q", args.chunk_q), ("mamba_chunk", args.mamba_chunk),
                 ("rwkv_chunk", args.rwkv_chunk)]:
        if v is not None:
            rc_over[k] = v
    if args.no_remat:
        rc_over["remat"] = False
    if args.no_mla_absorb:
        rc_over["mla_absorb"] = False
    fsdp = None if args.fsdp is None else (args.fsdp == "on")

    archs = ARCH_IDS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = ["pod1", "pod2"] if args.mesh == "both" else [args.mesh]
    n_ok = n_skip = n_fail = 0
    for arch in archs:
        for sh in shapes:
            for mk in meshes:
                label = f"{arch} x {sh} x {mk}"
                try:
                    t0 = time.time()
                    rec = run_cell(arch, sh, mk, rc_over, args.tag, fsdp,
                                   args.out_dir, dump_hlo=args.dump_hlo)
                    dt = time.time() - t0
                    if "skipped" in rec:
                        n_skip += 1
                        print(f"SKIP {label}: {rec['skipped']}", flush=True)
                    else:
                        n_ok += 1
                        r = rec["roofline"]
                        print(f"OK   {label}: {dt:6.1f}s "
                              f"compute={r['compute_s']:.3e}s "
                              f"memory={r['memory_s']:.3e}s "
                              f"coll={r['collective_s']:.3e}s "
                              f"dom={r['dominant']} "
                              f"frac={r.get('roofline_fraction', 0):.3f}",
                              flush=True)
                except Exception as e:
                    n_fail += 1
                    print(f"FAIL {label}: {e}", flush=True)
                    traceback.print_exc()
    print(f"\ndry-run done: ok={n_ok} skip={n_skip} fail={n_fail}",
          flush=True)
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
