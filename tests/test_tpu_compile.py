"""Compile-only checks for a described TPU v5e chip (nothing runs).

Every registered Pallas kernel, and the planned GEMMs qwen1.5-0.5b
serves through the facade, must get through Mosaic: tile legality,
integer MXU paths and VMEM limits are what interpret mode cannot see.
The topology is described inside a fixture, never at import, so every
test worker collects the same tests and only the one that runs this
file loads the TPU compiler.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import Target, best_plan
from repro.kernels import execute_plan, planned, registry
from repro.kernels.registry import DeviceRng

CHIP = Target(name="single_chip", mesh_shape=(1, 1))

#: One aligned size per registered spec (builder args).
SIZES = {
    "mm": (256, 256, 256),
    "bmm": (4, 128, 128, 128),
    "conv2d": (128, 256, 4, 4),
    "fir": (4096, 15),
    "fft2d_stage": (256, 256),
    "jacobi2d": (128, 256),
    "jacobi2d_ms": (64, 256, 3),
    "jacobi2d_9pt": (128, 256),
    "mttkrp": (256, 128, 32, 128),
}

KERNEL_CASES = [(name, dtype) for name in sorted(SIZES)
                for dtype in ("float32", "int8")
                if name != "fft2d_stage" or dtype == "float32"]
KERNEL_CASES += [("mm", "int16"), ("bmm", "int16")]

#: qwen1.5-0.5b facade GEMMs (k, n): attention projections, MLP up and
#: down, the tied LM head.
QWEN_GEMMS = [(1024, 1024), (1024, 2816), (2816, 1024), (1024, 151936)]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _compile_mosaic(plan, shapes):
    compiled = jax.jit(
        lambda *o: execute_plan(plan, *o, interpret=False)
    ).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("name,dtype", KERNEL_CASES)
def test_kernel_compiles_for_v5e(one_chip, name, dtype):
    spec = registry.get(name)
    rec = spec.builder(*SIZES[name], dtype)
    shapes = jax.eval_shape(lambda k: spec.operands(rec, DeviceRng(k)),
                            jax.random.PRNGKey(0))
    _compile_mosaic(best_plan(rec, CHIP), _on(one_chip, shapes))


@pytest.mark.parametrize("m", [4, 512], ids=["decode", "prefill"])
@pytest.mark.parametrize("k,n", QWEN_GEMMS,
                         ids=[f"{k}x{n}" for k, n in QWEN_GEMMS])
def test_qwen_facade_gemm_compiles_for_v5e(one_chip, k, n, m):
    plan = planned.plan_for("mm", (m, n, k), "bfloat16")
    assert plan is not None and plan.backend == "pallas"
    shapes = (jax.ShapeDtypeStruct((m, k), jnp.bfloat16),
              jax.ShapeDtypeStruct((k, n), jnp.bfloat16))
    _compile_mosaic(plan, _on(one_chip, shapes))


@pytest.mark.parametrize("pool_dtype,bs", [("bfloat16", 16),
                                           ("float8_e4m3fn", 32)])
def test_paged_attention_compiles_for_v5e(one_chip, pool_dtype, bs):
    """The paged decode attention kernel at qwen1.5-0.5b's serving shapes:
    16 lanes of 2048 rows, 24 layers, 16 kv heads of 64 (rows of 1024),
    bf16 queries; the fp8 pool in blocks of one 32-row tile."""
    from repro.configs.base import ModelConfig
    from repro.kernels import paged_attention as PA

    lanes, heads, row, seq = 16, 16, 1024, 2048
    blocks = lanes * seq // bs
    pool = jax.ShapeDtypeStruct((24, blocks, bs, row), pool_dtype)
    cfg = ModelConfig(name="qwen1.5-0.5b", family="dense", n_layers=24,
                      d_model=1024, n_heads=heads, n_kv_heads=heads,
                      d_ff=2816, vocab=151936)
    assert PA.engages(pool, cfg)
    new = jax.ShapeDtypeStruct((lanes, row), pool_dtype)
    shapes = (jax.ShapeDtypeStruct((lanes, heads, row), jnp.bfloat16),
              new, new, pool, pool, jax.ShapeDtypeStruct((), jnp.int32),
              jax.ShapeDtypeStruct((lanes, seq // bs), jnp.int32),
              jax.ShapeDtypeStruct((lanes,), jnp.int32))
    compiled = jax.jit(lambda *a: PA.paged_attention(
        *a, scale=0.125, interpret=False)).lower(
            *_on(one_chip, shapes)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_held_experts_compile_for_v5e(one_chip):
    """The held experts' in-place decode kernels at DeepSeek-V2-Lite's
    serving shapes: 26 MoE layers of 8 held experts, d 2048, ff 1408, 64
    lanes, bf16."""
    from repro.kernels import held_experts as HE

    layers, n, d, ff, lanes = 26, 8, 2048, 1408, 64
    up = jax.ShapeDtypeStruct((layers, n, d, ff), jnp.bfloat16)
    down = jax.ShapeDtypeStruct((layers, n, ff, d), jnp.bfloat16)
    assert HE.engages(up, up, down, lanes)
    shapes = (jax.ShapeDtypeStruct((lanes, d), jnp.bfloat16), up, up, down,
              jax.ShapeDtypeStruct((), jnp.int32),
              jax.ShapeDtypeStruct((lanes, n), jnp.float32))
    compiled = jax.jit(lambda *a: HE.held_experts_ffn(
        *a, interpret=False)).lower(*_on(one_chip, shapes)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2
