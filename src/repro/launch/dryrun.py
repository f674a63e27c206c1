import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh) cell.

This is the proof that the distribution config is coherent without real
hardware: ``jax.jit(step).lower(**ShapeDtypeStructs).compile()`` must succeed
on the 16x16 single-pod mesh AND the 2x16x16 multi-pod mesh for every cell,
and the compiled artifact yields memory_analysis / cost_analysis / the HLO
text that feeds the roofline pass (repro.launch.hlo_analysis).

Usage:
    python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod] [--out results.json]
"""
import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import (LM_SHAPES, LMConfig, TrainConfig, applicable_shapes,
                           get_config)
from repro.configs.registry import _ARCHS
from repro.launch.hlo_analysis import analyze_hlo, roofline_terms
from repro.launch.mesh import make_production_mesh
from repro.models import lm as lm_mod
from repro.models.sharding import batch_spec, param_specs


def _named(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def _replicated_like(mesh, tree):
    return jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)


def _batch_shardings(mesh, batch):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, batch_spec(s.shape, mesh)), batch)


def _decode_state_shardings(cfg, shape, mesh):
    """Shard KV caches / SSM states: batch dim -> ('pod','data') when it
    divides, cache sequence dim -> 'model' (flash-decoding layout)."""
    from repro.models.sharding import fsdp_axes, _axis_size
    shapes = lm_mod.decode_state_specs(cfg, shape.global_batch, shape.seq_len)
    dp = fsdp_axes(mesh)
    model_size = mesh.shape.get("model", 1)

    # prefer sharding the kv-head / ssm-head dim over 'model' when it
    # divides: a cache write (dynamic_update_slice at the decode index) on a
    # model-sharded SEQUENCE axis lowers to collective-permute chains
    # (measured: 4k+ permutes on zamba long_500k); head-sharded caches keep
    # writes local.
    head_dims = {cfg.num_kv_heads}
    if cfg.block_type == "mamba2":
        head_dims.add(2 * cfg.d_model // cfg.ssm_head_dim)   # ssm heads
    if cfg.block_type == "rwkv6":
        head_dims.add(cfg.d_model // cfg.ssm_head_dim)       # rwkv heads
    head_dims = {d for d in head_dims
                 if d % model_size == 0 and
                 d not in (shape.seq_len, shape.global_batch)}

    def spec(leaf):
        # never consider the leading stacked-layer axis as a head dim
        inner = leaf.shape[1:]
        shardable_head = any(d in head_dims for d in inner)
        used_model = False
        dims = [None]  # stacked-layer axis stays unsharded
        for d in inner:
            if d == shape.global_batch and dp is not None and \
                    d % _axis_size(mesh, dp) == 0 and shape.global_batch > 1:
                dims.append(dp)
            elif shardable_head and not used_model and d in head_dims:
                dims.append("model")
                used_model = True
            elif d == shape.seq_len and d % model_size == 0 \
                    and not shardable_head and not used_model:
                dims.append("model")
                used_model = True
            else:
                dims.append(None)
        # never shard two dims on the same axis
        seen, out = set(), []
        for a in dims:
            key = tuple(a) if isinstance(a, tuple) else a
            if key is not None and key in seen:
                out.append(None)
            else:
                out.append(a)
                if key is not None:
                    seen.add(key)
        return NamedSharding(mesh, P(*out))

    return jax.tree.map(spec, shapes), shapes


def build_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
               cfg_override=None, mesh=None):
    """Lower + compile one cell. Returns (compiled, lowered, info dict)."""
    cfg: LMConfig = cfg_override or get_config(arch)
    shape = LM_SHAPES[shape_name]
    if shape_name == "long_500k" and not cfg.sub_quadratic:
        raise ValueError(f"{arch} is pure full-attention; long_500k skipped "
                         f"by design (DESIGN.md §Arch-applicability)")
    mesh = mesh or make_production_mesh(multi_pod=multi_pod)

    key = jax.random.PRNGKey(0)
    params_struct = jax.eval_shape(lambda k: lm_mod.init_params(k, cfg), key)
    param_sh = _named(mesh, param_specs(params_struct, mesh))
    batch = lm_mod.input_specs(cfg, shape)
    batch_sh = _batch_shardings(mesh, batch)

    with jax.sharding.set_mesh(mesh):
        if shape.kind == "train":
            tcfg = TrainConfig()
            opt_init, train_step = lm_mod.make_train_step(cfg, tcfg)
            opt_struct = jax.eval_shape(opt_init, params_struct)
            from repro.optim.optimizers import AdamState
            opt_sh = AdamState(step=NamedSharding(mesh, P()),
                               mu=param_sh, nu=param_sh)
            step_struct = jax.ShapeDtypeStruct((), jnp.int32)
            out_struct = jax.eval_shape(train_step, params_struct, opt_struct,
                                        batch, step_struct)
            out_sh = (param_sh, opt_sh, _replicated_like(mesh, out_struct[2]))
            fn = jax.jit(lambda p, o, b, s: train_step(p, o, b, s),
                         in_shardings=(param_sh, opt_sh, batch_sh,
                                       NamedSharding(mesh, P())),
                         out_shardings=out_sh,
                         donate_argnums=(0, 1))
            lowered = fn.lower(params_struct, opt_struct, batch, step_struct)
        elif shape.kind == "prefill":
            def prefill(p, b):
                logits, _, _ = lm_mod.forward(p, cfg, b)
                return logits
            fn = jax.jit(prefill, in_shardings=(param_sh, batch_sh))
            lowered = fn.lower(params_struct, batch)
        else:  # decode
            serve = lm_mod.make_serve_step(cfg)
            state_sh, state_struct = _decode_state_shardings(cfg, shape, mesh)
            idx_struct = jax.ShapeDtypeStruct((), jnp.int32)
            fn = jax.jit(serve,
                         in_shardings=(param_sh, batch_sh, state_sh,
                                       NamedSharding(mesh, P())),
                         out_shardings=(None, state_sh),
                         donate_argnums=(2,))
            lowered = fn.lower(params_struct, batch, state_struct, idx_struct)

        t0 = time.time()
        compiled = lowered.compile()
        compile_s = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):  # jax 0.4.x: one dict per computation
        cost = cost[0] if cost else None
    info = {
        "arch": cfg.name, "shape": shape_name,
        "mesh": dict(mesh.shape), "num_devices": mesh.devices.size,
        "compile_s": round(compile_s, 1),
        "bytes_per_device": {
            "arguments": getattr(mem, "argument_size_in_bytes", None),
            "outputs": getattr(mem, "output_size_in_bytes", None),
            "temps": getattr(mem, "temp_size_in_bytes", None),
            "aliased": getattr(mem, "alias_size_in_bytes", None),
        },
        "xla_cost_analysis_flops": cost.get("flops") if cost else None,
    }
    return compiled, lowered, info


class _TPOnlyMesh:
    """Mesh view exposing only the 'model' axis to the param-spec rules:
    in population mode the ('pod','data') axes hold population members, so
    member-internal sharding is TP-only."""

    def __init__(self, mesh):
        self._mesh = mesh
        self.axis_names = ("model",)
        self.shape = {"model": mesh.shape["model"]}


def build_population_cell(arch: str, shape_name: str, n: int, *,
                          multi_pod: bool = False, mesh=None,
                          cfg_override=None):
    """Lower + compile the PAPER'S protocol at LM scale: one jit'd vmapped
    train step updating n population members, members sharded over the
    ('pod','data') mesh axes, each member TP-sharded over 'model'.  The
    global token budget of the shape is split across members (fair
    comparison against the n=1 cell)."""
    cfg: LMConfig = cfg_override or get_config(arch)
    shape = LM_SHAPES[shape_name]
    assert shape.kind == "train", "population dry-run targets train shapes"
    mesh = mesh or make_production_mesh(multi_pod=multi_pod)
    from repro.models.sharding import fsdp_axes
    pop_axes = fsdp_axes(mesh)

    key = jax.random.PRNGKey(0)
    member_struct = jax.eval_shape(lambda k: lm_mod.init_params(k, cfg), key)
    pop_struct = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype), member_struct)
    from repro.models.sharding import population_mode
    member_specs = param_specs(member_struct, _TPOnlyMesh(mesh))
    if "embed" in member_struct:
        # sharded-operand gathers with population-sharded indices trip an
        # XLA SPMD partitioner CHECK on CPU; replicate the member embedding
        # (it is small relative to a member's share of HBM).
        member_specs["embed"]["embedding"] = P(None, None)
    pop_specs = jax.tree.map(lambda sp: P(pop_axes, *sp), member_specs,
                             is_leaf=lambda x: isinstance(x, P))
    pop_sh = _named(mesh, pop_specs)

    per_member_batch = max(shape.global_batch // n, 1)
    batch = {"tokens": jax.ShapeDtypeStruct((n, per_member_batch,
                                             shape.seq_len), jnp.int32)}
    if cfg.frontend == "audio_frames":
        batch["embeds"] = jax.ShapeDtypeStruct(
            (n, per_member_batch, shape.seq_len, cfg.d_model),
            jnp.dtype(cfg.dtype))
    if cfg.frontend == "vision_patches":
        batch["patch_embeds"] = jax.ShapeDtypeStruct(
            (n, per_member_batch, cfg.num_frontend_positions, cfg.d_model),
            jnp.dtype(cfg.dtype))
    batch_sh = jax.tree.map(
        lambda s: NamedSharding(mesh, P(pop_axes, *([None] * (len(s.shape) - 1)))),
        batch)

    tcfg = TrainConfig()
    opt_init, train_step = lm_mod.make_train_step(cfg, tcfg)
    opt_struct = jax.eval_shape(jax.vmap(opt_init), pop_struct)
    from repro.optim.optimizers import AdamState
    opt_sh = AdamState(step=NamedSharding(mesh, P(pop_axes)),
                       mu=pop_sh, nu=pop_sh)
    hyper_struct = {"lr_scale": jax.ShapeDtypeStruct((n,), jnp.float32)}
    hyper_sh = {"lr_scale": NamedSharding(mesh, P(pop_axes))}
    step_struct = jax.ShapeDtypeStruct((), jnp.int32)

    def pop_step(params, opt, b, step, hypers):
        return jax.vmap(
            lambda p, o, bi, sc: train_step(p, o, bi, step, lr_scale=sc)
        )(params, opt, b, hypers["lr_scale"])

    with jax.sharding.set_mesh(mesh), population_mode():
        out_struct = jax.eval_shape(pop_step, pop_struct, opt_struct, batch,
                                    step_struct, hyper_struct)
        fn = jax.jit(pop_step,
                     in_shardings=(pop_sh, opt_sh, batch_sh,
                                   NamedSharding(mesh, P()), hyper_sh),
                     out_shardings=(pop_sh, opt_sh,
                                    _replicated_like(mesh, out_struct[2])),
                     donate_argnums=(0, 1))
        lowered = fn.lower(pop_struct, opt_struct, batch, step_struct,
                           hyper_struct)
        t0 = time.time()
        compiled = lowered.compile()
        compile_s = time.time() - t0

    mem = compiled.memory_analysis()
    info = {
        "arch": cfg.name, "shape": shape_name, "population": n,
        "mesh": dict(mesh.shape), "num_devices": mesh.devices.size,
        "compile_s": round(compile_s, 1),
        "bytes_per_device": {
            "arguments": getattr(mem, "argument_size_in_bytes", None),
            "outputs": getattr(mem, "output_size_in_bytes", None),
            "temps": getattr(mem, "temp_size_in_bytes", None),
            "aliased": getattr(mem, "alias_size_in_bytes", None),
        },
    }
    return compiled, lowered, info


def analyze_cell(compiled, info) -> dict:
    hlo = compiled.as_text()
    a = analyze_hlo(hlo)
    terms = roofline_terms(a)
    info = dict(info)
    info.update({
        "hlo_flops_per_device": a["flops"],
        "hlo_traffic_bytes_per_device": a["traffic_bytes"],
        "collective_bytes_per_device": a["collective_bytes"],
        "collective_counts": a["collective_counts"],
        **{k: v for k, v in terms.items()},
    })
    return info


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             analyze: bool = True, mesh=None) -> dict:
    compiled, lowered, info = build_cell(arch, shape_name,
                                         multi_pod=multi_pod, mesh=mesh)
    if analyze:
        info = analyze_cell(compiled, info)
    return info


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--population", type=int, default=0,
                    help="lower the paper's population-vectorized train step "
                         "for N members instead of the plain cell")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--no-analyze", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    cells = []
    if args.all:
        for a in _ARCHS:
            cfg = get_config(a)
            for s in applicable_shapes(cfg):
                cells.append((a, s))
    else:
        cells = [(args.arch, args.shape)]

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    results = []
    for arch, shape in cells:
        for mp in meshes:
            tag = f"{arch} x {shape} x {'2x16x16' if mp else '16x16'}"
            try:
                if args.population:
                    compiled, _, info = build_population_cell(
                        arch, shape, args.population, multi_pod=mp)
                    if not args.no_analyze:
                        info = analyze_cell(compiled, info)
                else:
                    info = run_cell(arch, shape, multi_pod=mp,
                                    analyze=not args.no_analyze)
                info["status"] = "ok"
                print(f"[dryrun] OK   {tag}: compile={info['compile_s']}s "
                      f"bottleneck={info.get('bottleneck')}", flush=True)
            except Exception as e:
                info = {"arch": arch, "shape": shape, "multi_pod": mp,
                        "status": "error", "error": f"{type(e).__name__}: {e}"}
                print(f"[dryrun] FAIL {tag}: {e}", flush=True)
                traceback.print_exc()
            results.append(info)
            if args.out:
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)
    n_bad = sum(r["status"] != "ok" for r in results)
    print(f"[dryrun] {len(results) - n_bad}/{len(results)} cells OK")
    return 1 if n_bad else 0


if __name__ == "__main__":
    sys.exit(main())
