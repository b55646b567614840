"""Distributed collective tests (8 host devices via subprocess)."""

import subprocess
import sys

_CODE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.compat import make_mesh, shard_map
from repro.parallel.collectives import quantized_psum, ring_reduce_scatter_matmul

rng = np.random.default_rng(0)

# --- ring reduce-scatter matmul == plain matmul ---
mesh = make_mesh((8,), ("tp",))
m, k, n = 64, 128, 32
x = rng.standard_normal((m, k)).astype(np.float32)
w = rng.standard_normal((k, n)).astype(np.float32)
fn = shard_map(lambda xl, wl: ring_reduce_scatter_matmul(xl, wl, "tp", 8),
               mesh=mesh, in_specs=(P(None, "tp"), P("tp", None)),
               out_specs=P("tp", None), check=False)
y = np.asarray(jax.jit(fn)(jnp.asarray(x), jnp.asarray(w)))
print("RING_OK" if np.allclose(y, x @ w, atol=1e-3) else "RING_FAIL")

# --- int8 TP matmul must accumulate exactly in int32 ---
# Regression: the pre-fix fp32 MACs drop low bits once per-shard partial
# sums pass 2^24 (values near 127 with k_loc=1280 drift by ~48 units);
# integer inputs now accumulate in int32 and match the oracle bit-exactly.
k8 = 10240
x8 = rng.integers(120, 128, size=(64, k8), dtype=np.int8)
w8 = rng.integers(120, 128, size=(k8, 32), dtype=np.int8)
y8 = np.asarray(jax.jit(fn)(jnp.asarray(x8), jnp.asarray(w8)))
ref8 = x8.astype(np.int64) @ w8.astype(np.int64)
print("RING_INT8_OK" if (y8.dtype == np.int32 and np.array_equal(y8, ref8))
      else ("RING_INT8_FAIL", y8.dtype, np.abs(y8.astype(np.int64) - ref8).max()))

# --- quantized psum: unbiased within quantization noise ---
g = rng.standard_normal((8, 256)).astype(np.float32) * 3
fn2 = shard_map(lambda gl: quantized_psum(gl, "dp", jax.random.PRNGKey(1)),
                mesh=make_mesh((8,), ("dp",)),
                in_specs=P("dp", None), out_specs=P("dp", None),
                check=False)
out = np.asarray(jax.jit(fn2)(jnp.asarray(g)))[0]
true = g.sum(0)
scale = np.abs(g).max() / 127.0
# error bounded by ~sqrt(8) quantization steps w.h.p.
err = np.abs(out - true)
print("QPSUM_OK" if err.max() < 8 * scale else ("QPSUM_FAIL", err.max(), scale))

# --- EP all-to-all MoE == TP-MoE == single-device MoE ---
import dataclasses
from repro.configs import get_smoke_config
from repro.models import moe as MOE
from repro.parallel.sharding import mesh_context

cfg = get_smoke_config("olmoe-1b-7b")
cfg = dataclasses.replace(cfg, dtype="float32", moe_capacity_factor=8.0)
p = MOE.init_moe(jax.random.PRNGKey(0), cfg)
x = jnp.asarray(rng.standard_normal((8, 4, cfg.d_model)), jnp.float32)

y_ref, aux_ref, _ = MOE.apply_moe(p, cfg, x)  # no mesh: dense path

mesh2 = make_mesh((2, 4), ("data", "model"))
with mesh_context(mesh2):
    y_tp, aux_tp, _ = jax.jit(lambda p, x: MOE.apply_moe(p, cfg, x))(p, x)
cfg_ep = dataclasses.replace(cfg, moe_ep=True)
with mesh_context(mesh2):
    y_ep, aux_ep, _ = jax.jit(lambda p, x: MOE.apply_moe(p, cfg_ep, x))(p, x)

# capacity semantics differ across shardings when tokens drop; with a high
# capacity factor nothing drops and all paths must agree.
tp_ok = np.allclose(np.asarray(y_tp), np.asarray(y_ref), atol=2e-4)
ep_ok = np.allclose(np.asarray(y_ep), np.asarray(y_ref), atol=2e-4)
print("MOE_TP_OK" if tp_ok else "MOE_TP_FAIL",
      "MOE_EP_OK" if ep_ok else "MOE_EP_FAIL")
"""


def test_distributed_collectives():
    proc = subprocess.run(
        [sys.executable, "-c", _CODE], capture_output=True, text=True,
        cwd=".", timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = proc.stdout
    assert "RING_OK" in out, out
    assert "RING_INT8_OK" in out, out
    assert "QPSUM_OK" in out, out
    assert "MOE_TP_OK" in out, out
    assert "MOE_EP_OK" in out, out
