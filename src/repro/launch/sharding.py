"""Sharding rules: parameter-path -> PartitionSpec (FSDP x TP x EP).

The model axis carries tensor parallelism (heads / ffn / experts / vocab);
the (pod, data) axes carry data parallelism and — when the policy enables
it — FSDP (ZeRO-3-style parameter+optimizer sharding). Rules are keyed by
the trailing parameter name; extra leading dims (scanned-stage stacking)
are padded with None.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeSpec
from repro.launch.mesh import dp_axes
from repro.models.transformer import RunConfig


@dataclass(frozen=True)
class ShardingPolicy:
    mode: str = "tp_fsdp"  # tp_fsdp | dp_zero1
    fsdp: bool = True  # (tp_fsdp) shard the non-TP weight dim over (pod,data)
    shard_cache_seq: bool = False  # long-context: shard KV cache over seq
    compress_grads: bool = False


def choose_policy(cfg: ArchConfig, shape: ShapeSpec, mesh: Mesh,
                  model_axis: int = 16) -> ShardingPolicy:
    """Memory-driven default.

    Small archs (<2.6B params) run pure data-parallel over the *whole*
    mesh with ZeRO-1 (params/grads replicated, Adam moments TP-sharded,
    batch over data x model): no TP collectives in the step, one grad
    all-reduce + param all-gather. This is what production would do for a
    1-2B model on a 256-chip pod — TP-16 on a 1B model drowns in
    resharding (measured in EXPERIMENTS.md SPerf).

    Larger archs use TP over `model` (+ FSDP over (pod,data) when
    TP-sharded state still would not fit: 12 bytes/param train state,
    budget ~4GB/chip).
    """
    from repro.models.transformer import count_params
    n = count_params(cfg)
    seq_shard = (shape.name == "long_500k")
    if n < 2.6e9:
        return ShardingPolicy(mode="dp_zero1", fsdp=False,
                              shard_cache_seq=seq_shard)
    if shape.kind == "train":
        need = n * 12 / model_axis
    else:
        need = n * 2 / model_axis
    return ShardingPolicy(mode="tp_fsdp", fsdp=need > 4e9,
                          shard_cache_seq=seq_shard)


# ---------------------------------------------------------------------------
# Parameter rules
# ---------------------------------------------------------------------------

# trailing-dims partition templates; "F" = fsdp axes, "M" = model axis
_RULES_2D_IN_OUT = {  # (d_in, d_out_tp): F, M
    "wq", "wk", "wv", "q_a", "q_b", "kv_a", "kv_b", "in_proj", "w_gate",
    "w_up", "ck", "cr", "wr", "wg", "mix_w1", "dw1", "dt_proj",
}
_RULES_2D_OUT_IN = {  # (d_tp, d_out): M, F
    "wo", "w_down", "out_proj", "cv", "x_proj",
}
_RULES_VEC_TP = {"bq", "bk", "bv", "conv_b", "dt_bias", "D"}
_REPLICATED = {
    "scale", "bias", "mix_mu", "mix_x", "mix_w2", "dw2", "w0", "bonus_u",
    "ln_x_scale", "ln_x_bias", "cmu_k", "cmu_r",
}


def _param_partition(path_keys, leaf_ndim: int, fsdp_axes) -> P:
    name = path_keys[-1]
    f = fsdp_axes if fsdp_axes else None
    if name == "embed":
        spec = ("model", f)
    elif name == "lm_head":
        spec = (f, "model")
    elif name == "router":
        spec = (f, None)
    elif name in ("w_gate", "w_up") and leaf_ndim >= 3:
        spec = ("model", f, None)  # MoE experts: EP over model
    elif name == "w_down" and leaf_ndim >= 3:
        spec = ("model", None, f)
    elif name in _RULES_2D_IN_OUT:
        spec = (f, "model")
    elif name in _RULES_2D_OUT_IN:
        spec = ("model", f)
    elif name == "conv_w":
        spec = (None, "model")
    elif name == "A_log":
        spec = ("model", None)
    elif name in _RULES_VEC_TP:
        spec = ("model",)
    elif name in _REPLICATED or name == "step":
        spec = ()
    else:
        spec = ()  # unknown: replicate (safe)
    spec = spec[:leaf_ndim]
    pad = leaf_ndim - len(spec)
    return tuple([None] * pad) + tuple(spec)


def _path_names(path) -> list:
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "idx"):
            out.append(str(p.idx))
        elif hasattr(p, "name"):
            out.append(str(p.name))
    return out


def param_shardings(cfg: ArchConfig, params_shape, mesh: Mesh,
                    policy: ShardingPolicy, *, force_tp: bool = False):
    """params_shape: eval_shape tree. Returns matching NamedSharding tree.

    Scanned stages stack a leading repeat dim on every leaf; rules are
    applied at the parameter's *intrinsic* rank and padded with None.
    dp_zero1 replicates parameters (force_tp=True still applies the TP
    rules — used for the ZeRO-1 optimizer moments).
    """
    if policy.mode == "dp_zero1" and not force_tp:
        repl = NamedSharding(mesh, P())
        return jax.tree.map(lambda _: repl, params_shape)
    fsdp_axes = dp_axes(mesh) if (policy.fsdp and policy.mode == "tp_fsdp"
                                  and not force_tp) else None

    def rule(path, leaf):
        names = _path_names(path)
        ndim = len(leaf.shape)
        if names and names[0] == "stages":
            stage_idx = int(names[1])
            if cfg.stages[stage_idx].repeat > 1:
                ndim -= 1  # leading scan-stacking dim
        spec = _param_partition(names, ndim, fsdp_axes)
        pad = len(leaf.shape) - len(spec)
        spec = P(*([None] * pad), *spec)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(rule, params_shape)


def opt_shardings(cfg, opt_shape, params_sharding_tree, mesh: Mesh,
                  policy: ShardingPolicy):
    """Adam moments follow the parameter shardings (tp_fsdp) or get the TP
    rules (dp_zero1 = ZeRO-1: moments sharded even though params are
    replicated); step is replicated."""
    if policy.mode == "dp_zero1":
        mt = param_shardings(cfg, opt_shape["m"], mesh, policy, force_tp=True)
        return {"m": mt, "v": mt, "step": NamedSharding(mesh, P())}
    return {
        "m": params_sharding_tree,
        "v": params_sharding_tree,
        "step": NamedSharding(mesh, P()),
    }


# ---------------------------------------------------------------------------
# Activation / batch / cache rules
# ---------------------------------------------------------------------------


def batch_axes(mesh: Mesh, policy: ShardingPolicy, batch_size: int):
    """Axes the batch dim is sharded over: the whole mesh for dp_zero1
    (falling back by divisibility), dp axes otherwise."""
    cands = []
    if policy.mode == "dp_zero1":
        cands = [dp_axes(mesh) + ("model",), ("data", "model")]
    cands += [dp_axes(mesh), ("data",)]
    for cand in cands:
        cand = tuple(a for a in cand if a in mesh.axis_names)
        n = 1
        for a in cand:
            n *= mesh.shape[a]
        if cand and batch_size % n == 0 and batch_size >= n:
            return cand
    return None


def batch_shardings(mesh: Mesh, has_frontend: bool, batch_size: int,
                    policy: ShardingPolicy = ShardingPolicy()):
    bspec = batch_axes(mesh, policy, batch_size)
    out = {"tokens": NamedSharding(mesh, P(bspec, None)),
           "labels": NamedSharding(mesh, P(bspec, None))}
    if has_frontend:
        out["frontend_embeds"] = NamedSharding(mesh, P(bspec, None, None))
    return out


def cache_partition(path_keys, leaf_ndim: int, *, dp, seq_shard: bool,
                    heads_ok: bool = False) -> P:
    """KV caches: batch over dp (or seq over dp for long-context) and —
    when the (padded) kv-head count divides the model axis — heads over
    `model`, matching the head-TP attention layout (otherwise decode
    resharding gathers the cache every step; EXPERIMENTS.md §Perf);
    recurrent states: feature dims over model."""
    name = path_keys[-1]
    h = "model" if heads_ok else None
    if name in ("k", "v"):  # (B, S, Hk, dh)
        spec = (None, dp, h, None) if seq_shard else (dp, None, h, None)
    elif name == "c_kv" or name == "k_rope":  # (B, S, r)
        spec = (None, dp, None) if seq_shard else (dp, None, None)
    elif name == "conv":  # (B, K-1, di)
        spec = (None, None, "model") if seq_shard else (dp, None, "model")
    elif name == "ssm":  # (B, di, ds)
        spec = (None, "model", None) if seq_shard else (dp, "model", None)
    elif name == "wkv":  # (B, H, dk, dv)
        spec = (None, "model", None, None) if seq_shard \
            else (dp, "model", None, None)
    elif name.startswith("shift"):  # (B, d)
        spec = (None, None) if seq_shard else (dp, None)
    else:
        spec = ()
    pad = leaf_ndim - len(spec)
    return P(*([None] * pad), *spec)


def cache_shardings(cache_shape, mesh: Mesh, policy: ShardingPolicy,
                    batch_size: int):
    dp = dp_axes(mesh)
    ndp = 1
    for a in dp:
        ndp *= mesh.shape[a]
    seq_shard = policy.shard_cache_seq or batch_size % ndp != 0 \
        or batch_size < ndp

    model = mesh.shape.get("model", 1)

    def rule(path, leaf):
        names = _path_names(path)
        heads_ok = (policy.mode == "tp_fsdp" and names
                    and names[-1] in ("k", "v") and len(leaf.shape) >= 2
                    and leaf.shape[-2] % model == 0)
        spec = cache_partition(names, len(leaf.shape), dp=dp,
                               seq_shard=seq_shard, heads_ok=heads_ok)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(rule, cache_shape)


def make_shard_fn(mesh: Mesh, policy: ShardingPolicy = ShardingPolicy(),
                  bsz: int = 0):
    """Builds RunConfig.shard: translate logical axis tokens to this mesh.

    Tokens: 'data' -> the policy's batch axes; 'model' -> model (dropped
    under dp_zero1 where the model axis carries batch); 'bh' -> the
    maximal axis combo whose product divides the dim (attention (B*H)
    super-batch). Non-divisible entries are dropped (replicated).
    """
    dp = batch_axes(mesh, policy, bsz) or dp_axes(mesh)
    model = mesh.shape.get("model", 1)
    model_token = None if (policy.mode == "dp_zero1"
                           and "model" in dp) else "model"

    def _axes_size(axes) -> int:
        n = 1
        for a in axes:
            n *= mesh.shape[a]
        return n

    def shard(x, spec_tuple):
        spec = []
        for i, s in enumerate(spec_tuple[: x.ndim]):
            dim = x.shape[i]
            if s == "data":
                spec.append(dp if dp and dim % _axes_size(dp) == 0 else None)
            elif s == "model":
                spec.append(model_token if model_token
                            and dim % model == 0 else None)
            elif s == "bh":
                chosen = None
                cands = [dp] if "model" in dp else [dp + ("model",), dp]
                cands += [("data",)]
                for cand in cands:
                    cand = tuple(a for a in cand if a in mesh.axis_names)
                    if cand and dim % _axes_size(cand) == 0:
                        chosen = cand
                        break
                spec.append(chosen)
            else:
                spec.append(s)
        spec += [None] * (x.ndim - len(spec))
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(*spec)))

    return shard


def make_expert_map(mesh: Mesh, policy: ShardingPolicy = ShardingPolicy(),
                    bsz: int = 0):
    """Builds RunConfig.expert_map: an MoE layer's routed part, once per
    device (``shard_map``). Each device routes its own tokens (the batch
    over the policy's batch axes) over every expert and computes the
    assignments to its slice of the experts (experts over ``model``
    where the policy gives the model axis to them, as the parameter
    rules do); the slices' outputs and counts are summed over ``model``,
    and the balance loss is averaged over the batch axes."""
    dp = batch_axes(mesh, policy, bsz) or ()
    ep = None if policy.mode == "dp_zero1" or "model" in dp else "model"

    def expert_map(run, params, x, first):
        def body(p, xs):
            n_local = p["w_gate"].shape[0]
            idx = jax.lax.axis_index(ep) if ep else 0
            y, aux, sizes = run(p, xs, first + idx * n_local)
            if dp:
                aux = jax.lax.pmean(aux, dp)
                sizes = jax.lax.psum(sizes, dp)
            rows, most = sizes.sum(), sizes.max()
            if ep:
                y = jax.lax.psum(y, ep)
                rows, most = jax.lax.psum(rows, ep), jax.lax.pmax(most, ep)
            return y, aux, rows, most

        experts = P(ep)
        specs = {"router": P(), "w_gate": experts, "w_up": experts,
                 "w_down": experts}
        tokens = P(dp or None)
        # no replication typing: the passes' cond joins an output that
        # varies over the mesh with an input that does not
        return jax.shard_map(body, mesh=mesh, in_specs=(specs, tokens),
                             out_specs=(tokens, P(), P(), P()),
                             check_vma=False)(params, x)

    return expert_map


def run_config_for(cfg: ArchConfig, shape: ShapeSpec, mesh: Mesh,
                   base: Optional[RunConfig] = None,
                   policy: ShardingPolicy = ShardingPolicy()) -> RunConfig:
    import dataclasses
    rc = base or RunConfig()
    return dataclasses.replace(
        rc, shard=make_shard_fn(mesh, policy, shape.global_batch),
        expert_map=make_expert_map(mesh, policy, shape.global_batch))
