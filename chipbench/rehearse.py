"""Compile each cell's programs for a described TPU v5e (``v5e:2x2``)
without a chip, and print what each needs per device.

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 chipbench/rehearse.py \
        [--cells mm-f32-1chip,...]

Nothing runs: the TPU compiler refuses what does not fit or does not
tile, and ``memory_analysis()`` gives the bytes of arguments, outputs and
temporaries per device.  Pallas kernels compile through Mosaic here
(interpret mode is for executing on a CPU, which this never does).
"""

from __future__ import annotations

import argparse
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from chipbench import harness  # noqa: E402

GiB = 2**30


def _report(name, compiled, t0):
    ma = compiled.memory_analysis()
    print(f"{name}: compiled in {time.perf_counter() - t0:.1f}s; per device "
          f"args {ma.argument_size_in_bytes / GiB:.3f} GiB, outputs "
          f"{ma.output_size_in_bytes / GiB:.3f} GiB, temps "
          f"{ma.temp_size_in_bytes / GiB:.3f} GiB, aliased "
          f"{ma.alias_size_in_bytes / GiB:.3f} GiB; tpu_custom_call "
          f"{'tpu_custom_call' in compiled.as_text()}", flush=True)


def _on(sharding, tree):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=sharding), tree)


def recurrence(cell, topo):
    from repro.compat import make_mesh
    from repro.core import Target, best_plan, lower_plan
    from repro.core.recurrence import matmul
    from repro.kernels import runtime

    cfg, s = cell.config, cell.settings
    ext = cfg["extents"]
    target = Target(name="rehearsal", mesh_shape=tuple(s["mesh"]))
    plan = best_plan(matmul(ext["i"], ext["j"], ext["k"], cfg["dtype"]),
                     target)
    shapes = [jax.ShapeDtypeStruct((ext["i"], ext["k"]), cfg["dtype"]),
              jax.ShapeDtypeStruct((ext["k"], ext["j"]), cfg["dtype"])]
    t0 = time.perf_counter()
    with jax.default_matmul_precision(cfg["precision"]):
        if s["backend"] == "pallas":
            fn = jax.jit(lambda a, b: runtime.execute_plan(
                plan, a, b, interpret=False))
            compiled = fn.lower(*_on(SingleDeviceSharding(topo.devices[0]),
                                     shapes)).compile()
        else:
            mesh = make_mesh(target.mesh_shape, target.mesh_axes,
                             devices=topo.devices[:4])
            sh = NamedSharding(mesh, P(*target.mesh_axes))
            fn = jax.jit(lower_plan(plan, backend=s["backend"], mesh=mesh),
                         in_shardings=(sh, sh), out_shardings=sh)
            compiled = fn.lower(*_on(sh, shapes)).compile()
    _report(f"{cell.name} call (blocks {plan.partition.block})", compiled,
            t0)


def serve(cell, topo):
    from repro.kernels import runtime

    from chipbench.drivers import serve as drv
    from chipbench.reference import qwen as ref

    # compile the kernels for the chip: interpret mode follows the
    # backend, which is the CPU here
    runtime.default_interpret = lambda: False
    s, config = cell.settings, cell.config
    one = SingleDeviceSharding(topo.devices[0])
    eng, shapes = drv.engine(config, s)
    params = _on(one, shapes)
    nb = s["lanes"] * s["max_seq"] // s["block_size"]
    pools = _on(one, jax.eval_shape(
        lambda: eng.api.paged_init(nb, s["block_size"], s["lanes"])))
    lanes, T = s["lanes"], s["max_seq"] // s["block_size"]
    i32 = jnp.int32
    args = [_on(one, jax.ShapeDtypeStruct(sh, dt)) for sh, dt in (
        ((lanes, 1), i32), ((lanes, T), i32), ((lanes,), i32),
        ((lanes,), jnp.bool_))]
    t0 = time.perf_counter()
    with eng._plan_ctx():
        dec = jax.jit(lambda p, pl, t, bt, pos, act: eng.api.paged_decode(
            p, pl, t, bt, pos, act)).lower(params, pools, *args).compile()
    _report(f"{cell.name} decode, {lanes} lanes x {s['max_seq']}", dec, t0)
    lo, hi = cell.traffic["prompt_len"]
    for b in sorted({eng.scheduler.bucket_for(hi),
                     eng.scheduler.bucket_for(lo)}):
        t0 = time.perf_counter()
        tok = _on(one, jax.ShapeDtypeStruct((1, b), i32))
        li = _on(one, jax.ShapeDtypeStruct((1,), i32))
        with eng._plan_ctx():
            pre = jax.jit(lambda p, t, i: eng.api.prefill(
                p, {"tokens": t}, b, last_index=i)).lower(
                params, tok, li).compile()
        _report(f"{cell.name} prefill bucket {b}", pre, t0)
    pad = s["check_pad"]
    t0 = time.perf_counter()
    items = tuple((k, config[k]) for k in ref._KEYS)
    rc = ref._logits.lower(
        params, _on(one, jax.ShapeDtypeStruct((pad,), i32)),
        _on(one, jax.ShapeDtypeStruct((pad,), i32)), items, None).compile()
    _report(f"{cell.name} reference, {pad} tokens", rc, t0)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cells", default="mm-f32-1chip,"
                    "qwen05b-closed-decode,qwen05b-open-prefill")
    args = ap.parse_args()
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for name in args.cells.split(","):
        cell = harness.load_cell(name)
        {"recurrence": recurrence, "serve": serve}[
            cell.settings["driver"]](cell, topo)


if __name__ == "__main__":
    main()
