import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ the 512 placeholder devices MUST be configured before ANY other import
#   (jax locks the device count on first init)

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell the appropriate step function is lowered against
ShapeDtypeStruct inputs (no allocation), compiled, and the artifacts
recorded:  memory_analysis (fits-per-device proof), cost_analysis
(FLOPs/bytes for the roofline), and the optimized HLO's collective bytes.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-32b \
        --shape train_4k --mesh both --out results/dryrun

Cells follow the assignment: long_500k only for sub-quadratic archs
(DESIGN.md §5); decode/long cells lower serve_step (one token against a
full cache), prefill cells lower the prompt pass, train cells the full
train step (grads + AdamW update).
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, SHAPES, cells_for, get_config
from repro.configs.base import ShapeSpec
from repro.core import roofline as RL
from repro.models import build_model
from repro.optim import adamw_init, adamw_update, cosine_schedule, opt_state_logical
from repro.parallel.sharding import (
    guard_spec,
    logical_spec_tree,
    mesh_context,
)
from repro.launch.mesh import make_production_mesh


def _shardings_for(mesh, ctx, logical_tree, shape_tree):
    """logical axes + SDS shapes -> NamedShardings with divisibility guard."""
    spec_tree = logical_spec_tree(ctx, logical_tree)

    def mk(spec, sds):
        return NamedSharding(mesh, guard_spec(mesh, spec, sds.shape))

    return jax.tree.map(
        mk, spec_tree, shape_tree,
        is_leaf=lambda x: isinstance(x, P))


def _abstract(tree, shardings=None):
    """Attach shardings to a SDS tree."""
    if shardings is None:
        return tree
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        tree, shardings)


def _adapt_cache_logical(cfg, logical, mesh):
    """Shard the cache: kv-heads over 'model' when divisible, else the
    sequence axis (GSPMD distributed decode attention)."""
    model = mesh.shape.get("model", 1)

    def adapt(ax):
        ax = list(ax)
        if "kv_heads" in ax:
            if cfg.n_kv_heads % model == 0 and cfg.n_kv_heads > 0:
                return tuple(ax)
            i = ax.index("kv_heads")
            ax[i] = None
            if len(ax) >= 3 and ax[2] is None:
                ax[2] = "seq_sp"  # seq axis of [L,B,S,H,hd]
            return tuple(ax)
        # MLA latent cache [L,B,S,lora]: always shard seq
        if cfg.use_mla and len(ax) == 4 and ax[2] is None and ax[0] == "layers":
            ax[2] = "seq_sp"
        return tuple(ax)

    return jax.tree.map(
        adapt, logical, is_leaf=lambda x: isinstance(x, tuple))


@dataclasses.dataclass
class CellResult:
    arch: str
    shape: str
    mesh: str
    ok: bool
    seconds: float
    error: str = ""
    flops: float = 0.0            # corrected (probes / unroll / attn adj)
    bytes_accessed: float = 0.0   # corrected
    flops_raw: float = 0.0        # as reported on the scanned program
    coll: dict | None = None      # corrected collective bytes
    memory: dict | None = None
    model_flops: float = 0.0
    accounting: str = ""


def _lower_one(cfg, shape, mesh, ctx, api):
    """Build + lower + compile the right step for this shape kind.
    Returns (cost, coll, memory_dict, hlo)."""
    p_log = api.param_logical()
    params_sds = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    p_sh = _shardings_for(mesh, ctx, p_log, params_sds)
    params_abs = _abstract(params_sds, p_sh)

    if shape.kind == "train":
        batch_sds = api.batch_specs(shape)
        b_sh = _shardings_for(
            mesh, ctx, api.batch_logical(), batch_sds)
        batch_abs = _abstract(batch_sds, b_sh)
        opt_sds = jax.eval_shape(adamw_init, params_sds)
        o_log = opt_state_logical(p_log)
        from repro.optim.adamw import AdamWState
        o_sh = AdamWState(
            m=_shardings_for(mesh, ctx, o_log.m, opt_sds.m),
            v=_shardings_for(mesh, ctx, o_log.v, opt_sds.v),
            count=NamedSharding(mesh, P()),
        )
        opt_abs = _abstract(opt_sds, o_sh)

        from repro.train.step import make_train_step
        train_step = make_train_step(api, cfg)

        lowered = jax.jit(
            train_step,
            donate_argnums=(0, 1),
        ).lower(params_abs, opt_abs, batch_abs,
                jax.ShapeDtypeStruct((), jnp.int32))
    elif shape.kind == "prefill":
        batch_sds = api.batch_specs(shape)
        b_sh = _shardings_for(
            mesh, ctx, api.batch_logical(), batch_sds)
        batch_abs = _abstract(
            {k: v for k, v in batch_sds.items() if k != "labels"},
            {k: v for k, v in b_sh.items() if k != "labels"})

        def prefill_step(params, batch):
            return api.prefill(params, batch, shape.seq_len)

        lowered = jax.jit(prefill_step).lower(params_abs, batch_abs)
    else:  # decode
        cache_sds = jax.eval_shape(
            lambda: api.init_cache(shape.global_batch, shape.seq_len))
        c_log = _adapt_cache_logical(cfg, api.cache_logical(), mesh)
        c_sh = _shardings_for(mesh, ctx, c_log, cache_sds)
        cache_abs = _abstract(cache_sds, c_sh)
        tok_sds = jax.ShapeDtypeStruct(
            (shape.global_batch, 1), jnp.int32)
        tok_sh = NamedSharding(
            mesh, guard_spec(mesh, ctx.spec("batch", None),
                             tok_sds.shape))
        tok_abs = jax.ShapeDtypeStruct(
            tok_sds.shape, tok_sds.dtype, sharding=tok_sh)

        def serve_step(params, cache, tokens):
            return api.decode(params, cache, tokens)

        lowered = jax.jit(
            serve_step, donate_argnums=(1,)
        ).lower(params_abs, cache_abs, tok_abs)

    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    coll = RL.collective_bytes(hlo)
    mem_d = {
        "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
        "output_bytes": getattr(mem, "output_size_in_bytes", 0),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
        "generated_code_bytes": getattr(
            mem, "generated_code_size_in_bytes", 0),
    }
    return cost, coll, mem_d


def _model_flops(cfg, shape) -> float:
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n_active * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.global_batch * shape.seq_len
    return 2.0 * n_active * shape.global_batch


def _parse_override(kv: str):
    k, v = kv.split("=", 1)
    for cast in (int, float):
        try:
            return k, cast(v)
        except ValueError:
            pass
    if v in ("true", "True"):
        return k, True
    if v in ("false", "False"):
        return k, False
    return k, v


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               overrides: dict | None = None) -> CellResult:
    from repro.launch import accounting as ACC

    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    t0 = time.time()
    exact_families = ("encdec",)  # small enough to unroll exactly

    with mesh_context(mesh, multi_pod=multi_pod,
                      fsdp=cfg.fsdp) as ctx:
        if cfg.family in exact_families:
            # unrolled layer loop: HLO accounting is exact
            cfg_run = dataclasses.replace(cfg, scan_unroll=True)
            api = build_model(cfg_run)
            cost, coll_raw, mem_d = _lower_one(cfg_run, shape, mesh, ctx,
                                               api)
            flops = float(cost.get("flops", 0.0))
            nbytes = float(cost.get("bytes accessed", 0.0))
            coll = {k: float(coll_raw.get(k, 0)) for k in
                    ("all-gather", "all-reduce", "reduce-scatter",
                     "all-to-all", "collective-permute")}
            flops_raw = flops
            accounting = "unrolled"
        else:
            # 1. the real scanned program: compile proof + memory analysis
            api = build_model(cfg)
            cost0, coll0, mem_d = _lower_one(cfg, shape, mesh, ctx, api)
            flops_raw = float(cost0.get("flops", 0.0))
            # 2. L=1 / L=2 unrolled probes at full global shapes
            small, big, _, scaling = ACC.probe_configs(cfg)
            api1 = build_model(small)
            cost1, coll1, _ = _lower_one(small, shape, mesh, ctx, api1)
            api2 = build_model(big)
            cost2, coll2, _ = _lower_one(big, shape, mesh, ctx, api2)
            flops, nbytes, coll = ACC.combine_probe(
                cost1, coll1, cost2, coll2, scaling)
            accounting = f"probe(L1,L2,x{scaling})"

        # 3. analytic blockwise-attention addendum (per-device share)
        adj = ACC.attention_adjustment(cfg, shape, shape.kind)
        if adj:
            flops += adj / mesh.devices.size
            accounting += "+attn_analytic"

    dt = time.time() - t0
    return CellResult(
        arch=arch, shape=shape_name, mesh=mesh_name, ok=True, seconds=dt,
        flops=flops, bytes_accessed=nbytes, flops_raw=flops_raw,
        coll=coll, memory=mem_d, model_flops=_model_flops(cfg, shape),
        accounting=accounting,
    )


def run_cells(archs, shapes, meshes, out_dir, overrides=None, tag=""):
    os.makedirs(out_dir, exist_ok=True)
    results = []
    for arch in archs:
        allowed = cells_for(arch)
        for shape_name in shapes:
            if shape_name not in allowed:
                print(f"SKIP {arch} x {shape_name} (long-context rule)")
                continue
            for mp in meshes:
                mesh_name = "2x16x16" if mp else "16x16"
                cell_tag = f"{arch}__{shape_name}__{mesh_name}" + (
                    f"__{tag}" if tag else "")
                path = os.path.join(out_dir, cell_tag + ".json")
                if os.path.exists(path):
                    with open(path) as f:
                        cached = json.load(f)
                    if cached.get("ok"):
                        print(f"CACHED {cell_tag}")
                        results.append(cached)
                        continue
                    os.remove(path)  # retry failures
                print(f"LOWER {cell_tag} ...", flush=True)
                try:
                    res = lower_cell(arch, shape_name, mp,
                                     overrides=overrides)
                except Exception as e:  # noqa: BLE001
                    res = CellResult(
                        arch=arch, shape=shape_name, mesh=mesh_name,
                        ok=False, seconds=0.0,
                        error=f"{type(e).__name__}: {e}\n"
                              f"{traceback.format_exc()[-2000:]}")
                d = dataclasses.asdict(res)
                with open(path, "w") as f:
                    json.dump(d, f, indent=1)
                results.append(d)
                status = "OK" if res.ok else "FAIL"
                print(f"  -> {status} ({res.seconds:.1f}s)"
                      + ("" if res.ok else f"\n{res.error[:500]}"),
                      flush=True)
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--override", action="append", default=[],
                    help="cfg field override, e.g. --override moe_ep=true")
    ap.add_argument("--tag", default="",
                    help="suffix for result files (variant runs)")
    args = ap.parse_args()
    overrides = dict(_parse_override(kv) for kv in args.override) or None

    archs = ARCHS if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    results = run_cells(archs, shapes, meshes, args.out,
                        overrides=overrides, tag=args.tag)
    n_ok = sum(r["ok"] for r in results)
    print(f"\n==== dry-run: {n_ok}/{len(results)} cells OK ====")
    for r in results:
        if not r["ok"]:
            print(f"FAILED: {r['arch']} x {r['shape']} x {r['mesh']}")
    return 0 if n_ok == len(results) else 1


if __name__ == "__main__":
    raise SystemExit(main())
