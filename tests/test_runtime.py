"""Plan-driven runtime tests: execute_plan dispatch vs the jnp oracles,
the mapper's LRU plan cache, and the helpers every kernel shares
(compiler params, interpret resolution, Mosaic-legal tiles, the MXU
contraction)."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import Target, best_plan
from repro.core import conv2d as conv2d_rec
from repro.core import fft2d_stage, fir as fir_rec, matmul as matmul_rec
from repro.core.mapper import map_recurrence, plan_cache_clear, plan_cache_info
from repro.kernels import execute_plan, ref, runtime

RNG = np.random.default_rng(7)
CHIP = Target(name="single_chip", mesh_shape=(1, 1))


def _mk(shape, dtype):
    if dtype.startswith("int"):
        return jnp.asarray(RNG.integers(-10, 10, shape).astype(dtype))
    return jnp.asarray(RNG.standard_normal(shape).astype(np.float32))


# ---------------------------------------------------------------------------
# execute_plan dispatch vs ref oracles
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "int8", "int16"])
def test_execute_plan_mm(dtype):
    m, n, k = 64, 48, 32
    plan = best_plan(matmul_rec(m, n, k, dtype), CHIP)
    a, b = _mk((m, k), dtype), _mk((k, n), dtype)
    out = execute_plan(plan, a, b)
    atol = 0 if dtype.startswith("int") else 1e-3
    np.testing.assert_allclose(
        np.asarray(out, np.float64),
        np.asarray(ref.matmul(a, b), np.float64), atol=atol, rtol=1e-4)


@pytest.mark.parametrize("dtype", ["float32", "int8", "int16"])
def test_execute_plan_conv2d(dtype):
    p = q = 4
    img, filt = _mk((32, 30), dtype), _mk((p, q), dtype)
    oh, ow = 32 - p + 1, 30 - q + 1
    plan = best_plan(conv2d_rec(oh, ow, p, q, dtype), CHIP)
    out = execute_plan(plan, img, filt)
    atol = 0 if dtype.startswith("int") else 1e-3
    np.testing.assert_allclose(
        np.asarray(out, np.float64),
        np.asarray(ref.conv2d(img, filt), np.float64), atol=atol, rtol=1e-4)


@pytest.mark.parametrize("dtype", ["float32", "int8", "int16"])
def test_execute_plan_fir(dtype):
    taps = 15
    x, h = _mk((256,), dtype), _mk((taps,), dtype)
    plan = best_plan(fir_rec(256 - taps + 1, taps, dtype), CHIP)
    out = execute_plan(plan, x, h)
    atol = 0 if dtype.startswith("int") else 1e-3
    np.testing.assert_allclose(
        np.asarray(out, np.float64),
        np.asarray(ref.fir(x, h), np.float64), atol=atol, rtol=1e-4)


def test_execute_plan_fft2d_nonsquare():
    """Stage 2 contracts over the column extent; tiles must divide both."""
    xr, xi = _mk((64, 96), "float32"), _mk((64, 96), "float32")
    plan = best_plan(fft2d_stage(64, 96), CHIP)
    o_re, o_im = execute_plan(plan, xr, xi)
    e_re, e_im = ref.fft2d(xr, xi)
    np.testing.assert_allclose(np.asarray(o_re), np.asarray(e_re),
                               atol=1.0, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(o_im), np.asarray(e_im),
                               atol=1.0, rtol=1e-3)


def test_execute_plan_fft2d():
    xr, xi = _mk((32, 32), "float32"), _mk((32, 32), "float32")
    plan = best_plan(fft2d_stage(32, 32), CHIP)
    o_re, o_im = execute_plan(plan, xr, xi)
    e_re, e_im = ref.fft2d(xr, xi)
    np.testing.assert_allclose(np.asarray(o_re), np.asarray(e_re),
                               atol=0.5, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(o_im), np.asarray(e_im),
                               atol=0.5, rtol=1e-3)


def test_execute_plan_arity_check():
    plan = best_plan(matmul_rec(32, 32, 32), CHIP)
    a = _mk((32, 32), "float32")
    with pytest.raises(ValueError, match="expects 2 operands"):
        execute_plan(plan, a)


# ---------------------------------------------------------------------------
# plan-derived kernel parameters
# ---------------------------------------------------------------------------

def test_grid_semantics_from_plan():
    mm = matmul_rec(64, 64, 64)
    assert runtime.grid_semantics(mm, ("i", "j", "k")) == (
        "parallel", "parallel", "arbitrary")
    conv = conv2d_rec(16, 16, 4, 4)
    assert runtime.grid_semantics(conv, ("h", "w", ("p", "q"))) == (
        "parallel", "parallel", "arbitrary")
    f = fir_rec(128, 15)
    assert runtime.grid_semantics(f, ("n",)) == ("parallel",)


def test_plan_kernel_kwargs_match_partition_blocks():
    plan = best_plan(matmul_rec(256, 256, 256), CHIP)
    kw = runtime.plan_kernel_kwargs(plan)
    blk = plan.partition.block
    assert (kw["bm"], kw["bn"], kw["bk"]) == (blk["i"], blk["j"], blk["k"])
    assert kw["dimension_semantics"] == ("parallel", "parallel", "arbitrary")


def test_packing_ladder_shared_with_partition():
    """The runtime's dtype ladder IS core/partition's — no drift possible."""
    from repro.core import partition as part

    assert runtime.DTYPE_BYTES is part.DTYPE_BYTES
    assert runtime.PACKING is part.PACKING
    assert runtime.PACKING_TPU is part.PACKING_TPU
    assert runtime.packing_factor("int8", "tpu") == part.PACKING_TPU["int8"]
    assert runtime.packing_factor("int8", "aie") == part.PACKING["int8"]
    assert runtime.packing_factor("unknown_dtype") == 1.0


def test_compiler_params_portable():
    params = runtime.compiler_params(
        dimension_semantics=("parallel", "arbitrary"))
    assert tuple(params.dimension_semantics) == ("parallel", "arbitrary")
    with pytest.raises(TypeError):  # an unknown kwarg is never dropped
        runtime.compiler_params(dimension_semantics=("parallel",),
                                not_a_real_compiler_knob=1)


def test_interpret_follows_backend():
    assert runtime.resolve_interpret(None) is True  # the CPU test backend
    assert runtime.resolve_interpret(False) is False  # described-chip compiles


@pytest.mark.parametrize("ext,req,align,want", [
    (2816, 32, 128, 128),     # lane request below the tile rounds up
    (64, 8, 128, 64),         # ... and stops at the whole dim
    (151936, 128, 128, 128),
    (4, 128, 8, 4),           # decode rows: the whole dim
    (200, 64, 16, 64),        # bf16 sublanes: already a multiple of 16
    (200, 20, 32, 32),        # int8 sublanes round up to 32
])
def test_tile_is_mosaic_legal(ext, req, align, want):
    t = runtime.tile(ext, req, align)
    assert t == want
    assert t == ext or t % align == 0


@pytest.mark.parametrize("ext,req,align,want", [
    (8192, 128, 128, 128),
    (96, 32, 128, 96),        # no 128-multiple divides 96: the whole dim
    (384, 512, 128, 128),     # 384 itself is the whole dim; 256 ∤ 384
    (64, 32, 8, 32),
])
def test_divisor_tile_divides_exactly(ext, req, align, want):
    t = runtime.divisor_tile(ext, req, align)
    assert t == want and ext % t == 0


def test_sublanes_by_dtype():
    assert [runtime.sublanes(d) for d in
            ("float32", "bfloat16", "int16", "int8")] == [8, 16, 16, 32]


def test_mxu_dot_int16_limbs_exact_at_range_edges():
    """The three-limb int16 product equals the widened int32 dot modulo
    2**32, including the extremes of the int16 range (whose products
    overflow int32 when summed)."""
    edge = np.array([-32768, -32767, -129, -128, -1, 0, 1, 127, 128,
                     16383, 16384, 32639, 32640, 32767], np.int16)
    a = np.resize(edge, (16, 32)).astype(np.int16)
    b = np.resize(edge[::-1], (32, 8)).astype(np.int16)
    want = (a.astype(np.int64) @ b.astype(np.int64)) % 2**32
    got = np.asarray(runtime.mxu_dot(jnp.asarray(a), jnp.asarray(b)))
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got.astype(np.int64) % 2**32, want)


@pytest.mark.parametrize("da,db", [("int32", "int16"), ("int32", "int32")])
def test_mxu_dot_wide_int_limbs_exact(da, db):
    """int32 operands (a fused chain's accumulator feeding the next GEMM)
    split into five limbs; the result still wraps like the int32 dot."""
    rng = np.random.default_rng(3)
    lo_a, hi_a = np.iinfo(da).min, np.iinfo(da).max
    lo_b, hi_b = np.iinfo(db).min, np.iinfo(db).max
    a = rng.integers(lo_a, hi_a, (8, 16), endpoint=True).astype(da)
    b = rng.integers(lo_b, hi_b, (16, 8), endpoint=True).astype(db)
    want = (a.astype(np.int64) @ b.astype(np.int64)) % 2**32
    got = np.asarray(runtime.mxu_dot(jnp.asarray(a), jnp.asarray(b)))
    np.testing.assert_array_equal(got.astype(np.int64) % 2**32, want)


# ---------------------------------------------------------------------------
# plan cache
# ---------------------------------------------------------------------------

def test_plan_cache_hits_and_determinism():
    plan_cache_clear()
    rec = matmul_rec(128, 128, 128)
    p1 = best_plan(rec, CHIP)
    misses_after_first = plan_cache_info().misses
    # equal-but-distinct recurrence/target values must hit the cache
    p2 = best_plan(matmul_rec(128, 128, 128),
                   Target(name="single_chip", mesh_shape=(1, 1)))
    ci = plan_cache_info()
    assert ci.misses == misses_after_first
    assert ci.hits >= 1
    assert p1 == p2  # deterministic: identical plan for identical inputs
    assert p1.describe() == p2.describe()


def test_plan_cache_returns_fresh_list():
    plan_cache_clear()
    rec = matmul_rec(64, 64, 64)
    plans = map_recurrence(rec, CHIP)
    plans.clear()  # caller mutation must not corrupt the cache
    assert map_recurrence(rec, CHIP)


def test_plan_cache_mutation_isolated():
    """Plans carry mutable dicts; a caller tweaking one must not poison
    the cache for every later caller (plans are deep-copied on return)."""
    plan_cache_clear()
    rec = matmul_rec(64, 64, 64)
    p = best_plan(rec, CHIP)
    original = p.partition.block["k"]
    p.partition.block["k"] = 1
    p.plio_assignment["__poison__"] = 0
    p2 = best_plan(rec, CHIP)
    assert p2.partition.block["k"] == original
    assert "__poison__" not in p2.plio_assignment


def test_fft2d_stage_backends_agree():
    """xla and pallas backends share the (x_re, x_im) -> (re, im) contract
    for fft2d_stage plans (the systolic/allgather hooks honour the same
    contract — covered by the subprocess parity sweep)."""
    from repro.core import lower_plan

    plan = best_plan(fft2d_stage(32, 32), CHIP)
    xr, xi = _mk((32, 32), "float32"), _mk((32, 32), "float32")
    x_re, x_im = lower_plan(plan, backend="xla")(xr, xi)
    p_re, p_im = lower_plan(plan, backend="pallas")(xr, xi)
    np.testing.assert_allclose(np.asarray(p_re), np.asarray(x_re),
                               atol=0.5, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(p_im), np.asarray(x_im),
                               atol=0.5, rtol=1e-3)


def test_compile_cache_follows_env_else_checkout(monkeypatch, tmp_path):
    """Where ``JAX_COMPILATION_CACHE_DIR`` is set the helper sets nothing
    (JAX reads the variable itself); the fallback is one fixed,
    gitignored directory of the checkout."""
    import jax

    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    root = compile_cache.CHECKOUT_CACHE_DIR.parent
    assert (root / "chip_smoke.py").exists()
    assert ".jax_cache/" in (root / ".gitignore").read_text().splitlines()
