"""The paged decode attention kernel (``kernels.paged_attention``) in the
Pallas interpreter at tiny, Mosaic-legal sizes (rows of 128 values),
against the masked path it replaces, and the rule and counters that say
where it runs.

Tolerances come from the compute dtype: the kernel feeds the values
matmul the unnormalised probabilities in the compute dtype where the
masked path feeds the normalised ones, so the two differ by a few of its
rounding steps (bfloat16: 2**-8; float32: 2**-23)."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.kernels import paged_attention as PA
from repro.models import build_model
from repro.models import layers as L
from repro.serve import PagedServeEngine
from repro.serve.scheduler import SchedulerConfig

LAYERS, LANES, SEQ = 2, 4, 128
TOL = {"bfloat16": 3e-2, "float32": 2e-5}
#: (compute dtype, pool dtype, block size): an fp8 block is a whole
#: 32-row sublane tile
POOLS = {"f32": ("float32", "float32", 16), "bf16": ("bfloat16", "bfloat16", 16),
         "fp8": ("bfloat16", "float8_e4m3fn", 32)}


def _cfg(group=1, dtype="float32", kv="bfloat16"):
    """The qwen smoke model with rows of 2 kv heads x 64 = 128 values."""
    return dataclasses.replace(
        get_smoke_config("qwen1.5-0.5b"), n_heads=2 * group, n_kv_heads=2,
        head_dim=64, dtype=dtype, kv_cache_dtype=kv)


def _tables(bs):
    """Each lane's SEQ // bs blocks drawn from a permuted pool of twice as
    many: neither contiguous nor in order.  Lane 3 is inactive and its
    table still names lane 0's blocks, as a released lane's table does
    once its blocks went to another lane."""
    per, nb = SEQ // bs, 2 * LANES * SEQ // bs
    t = np.random.default_rng(5).permutation(nb)[:LANES * per]
    t = t.reshape(LANES, per)
    t[3] = t[0]
    return jnp.asarray(t, jnp.int32), nb


def _pools(cfg, bs, nb, seed=3):
    shape = (LAYERS, nb, bs, cfg.n_kv_heads * cfg.hd)
    kk, kv = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(kk, shape).astype(cfg.kv_cache_dtype),
            jax.random.normal(kv, shape).astype(cfg.kv_cache_dtype))


def _masked(p, cfg, x, pool_k, pool_v, layer, tables, pos):
    """The masked path on gathered rows, with the new row at ``pos``."""
    b = x.shape[0]
    q, k, v = L._qkv(p, cfg, x, pos[:, None])
    k = k.reshape(b, -1).astype(pool_k.dtype)
    v = v.reshape(b, -1).astype(pool_v.dtype)
    heads = (cfg.n_kv_heads, cfg.hd)
    kseq = L.with_row_at(L.paged_gather(pool_k, tables, layer), k, pos)
    vseq = L.with_row_at(L.paged_gather(pool_v, tables, layer), v, pos)
    return L._masked_decode_attention(
        p, cfg, q, kseq.reshape(b, -1, *heads), vseq.reshape(b, -1, *heads),
        pos, sites=("attn.paged_scores", "attn.paged_values"))


@pytest.mark.parametrize("pool", sorted(POOLS))
@pytest.mark.parametrize("group", [1, 2, 4])
@pytest.mark.parametrize("where", ["0", "bs-1", "bs", "middle", "max_seq-1"])
def test_kernel_matches_masked_attention(where, group, pool):
    """Lane 0 at the position under test, lane 1 ending mid-block, lane 2
    at the horizon: each active lane's attention output, kernel against
    the masked path over the gathered rows.  Lane 3 is inactive, its
    table names lane 0's blocks: it reads none of them and stays finite."""
    dtype, kv, bs = POOLS[pool]
    cfg = _cfg(group, dtype=dtype, kv=kv)
    tables, nb = _tables(bs)
    p = L.init_attention(jax.random.PRNGKey(1), cfg)
    pool_k, pool_v = _pools(cfg, bs, nb)
    assert PA.engages(pool_k, cfg)
    at = {"0": 0, "bs-1": bs - 1, "bs": bs, "middle": SEQ // 2 + 5,
          "max_seq-1": SEQ - 1}[where]
    pos = jnp.asarray([at, 37, SEQ - 1, 50], jnp.int32)
    active = jnp.asarray([True, True, True, False])
    x = jax.random.normal(jax.random.PRNGKey(2),
                          (LANES, 1, cfg.d_model)).astype(dtype)
    layer = jnp.int32(1)
    got, k, v = jax.jit(functools.partial(
        L.apply_attention_decode_stacked, p, cfg))(
            x, pool_k, pool_v, layer, tables, pos, active)
    want = jax.jit(functools.partial(_masked, p, cfg))(
        x, pool_k, pool_v, layer, tables, pos)
    got = np.asarray(got, np.float32)
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got[:3], np.asarray(want, np.float32)[:3],
                               rtol=TOL[dtype], atol=TOL[dtype])
    assert k.dtype == pool_k.dtype and v.dtype == pool_v.dtype


@pytest.mark.parametrize("pool", sorted(POOLS))
def test_kernel_reads_nothing_at_or_past_each_lanes_rows(pool):
    """The raw kernel: rows at or past a lane's count change nothing
    (poisoned with NaN here, an inactive lane's whole table with them),
    and a lane with no pooled rows returns its new row's value."""
    dtype, kv, bs = POOLS[pool]
    cfg = _cfg(dtype=dtype, kv=kv)
    tables, nb = _tables(bs)
    pool_k, pool_v = _pools(cfg, bs, nb)
    rows = jnp.asarray([0, 37, SEQ - 1, 0], jnp.int32)
    width = cfg.n_kv_heads * cfg.hd
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (LANES, cfg.n_heads, width)).astype(dtype)
    k_new, v_new = (jax.random.normal(k, (LANES, width)).astype(kv)
                    for k in ks[1:])
    run = functools.partial(PA.paged_attention, q, k_new, v_new,
                            layer=jnp.int32(0), tables=tables, rows=rows,
                            scale=0.125)
    clean = np.asarray(run(pool_k=pool_k, pool_v=pool_v), np.float32)
    # lane 1's rows 37.. (its block 37 // bs from row 37 % bs on, and
    # every later block) and every block of lane 3's (= lane 0's) table
    blk = np.asarray(tables)

    def poison(pool):
        pool = pool.at[0, blk[1, 37 // bs], 37 % bs:].set(jnp.nan)
        return pool.at[0, blk[1, 37 // bs + 1:]].set(jnp.nan).at[
            0, blk[3]].set(jnp.nan)

    poisoned = np.asarray(run(pool_k=poison(pool_k), pool_v=poison(pool_v)),
                          np.float32)
    np.testing.assert_array_equal(clean, poisoned)
    assert np.all(np.isfinite(clean))
    for lane in (0, 3):
        np.testing.assert_allclose(
            clean[lane], np.broadcast_to(
                np.asarray(v_new[lane].astype(dtype), np.float32),
                clean[lane].shape), rtol=1e-6)


@functools.lru_cache(maxsize=None)
def _model(group=1):
    cfg = _cfg(group)
    return cfg, build_model(cfg).init(jax.random.PRNGKey(42))


def _step(cfg, params, pools, tokens, tables, pos, active):
    return jax.jit(lambda *a: build_model(cfg).paged_decode(params, *a))(
        pools, tokens, tables, pos, active)


@pytest.mark.parametrize("group", [1, 2])
def test_decode_step_kernel_against_masked_path(group, monkeypatch):
    """A whole ``paged_decode`` step: logits of the active lanes within
    the float32 tolerance of the masked path.  The pools are bit-equal
    but for the new rows of the layers after the first, which are
    projected from the attention's output and so carry its rounding
    (within a step of the bfloat16 pool)."""
    cfg, params = _model(group)
    tables, nb = _tables(16)
    pk, pv = _pools(cfg, 16, nb)
    pools = {"k": pk, "v": pv}
    pos = np.asarray([0, 37, SEQ - 1, 50])
    active = jnp.asarray([True, True, True, False])
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, cfg.vocab, (LANES, 1)),
        jnp.int32)
    assert build_model(cfg).paged_kernel(pools)
    got_logits, got_pools = _step(cfg, params, pools, tokens, tables,
                                  jnp.asarray(pos, jnp.int32), active)
    monkeypatch.setattr(PA, "engages", lambda pool, cfg: False)
    want_logits, want_pools = _step(cfg, params, pools, tokens, tables,
                                    jnp.asarray(pos, jnp.int32), active)
    np.testing.assert_allclose(np.asarray(got_logits[:3]),
                               np.asarray(want_logits[:3]),
                               rtol=TOL["float32"], atol=TOL["float32"])
    blk = np.asarray(tables)[np.arange(3), pos[:3] // 16]
    at = (slice(1, None), blk, pos[:3] % 16)
    for k in pools:
        got = np.asarray(got_pools[k], np.float32)
        want = np.asarray(want_pools[k], np.float32)
        np.testing.assert_allclose(got[at], want[at], rtol=2 ** -7)
        got[at] = want[at] = 0
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("shape,dtype,engages", [
    ((2, 8, 16, 128), "bfloat16", True),
    ((2, 8, 8, 128), "float32", True),
    ((2, 8, 32, 128), "float8_e4m3fn", True),
    ((2, 8, 8, 128), "bfloat16", False),        # half a bf16 sublane tile
    ((2, 8, 16, 128), "float8_e4m3fn", False),  # half an fp8 sublane tile
    ((2, 8, 16, 64), "bfloat16", False),        # half a lane tile
    ((2, 8, 16, 128), "float16", False),
    ((2, 8, 16, 2, 64), "bfloat16", False),     # rows not flattened
], ids=["bf16", "f32-bs8", "fp8-bs32", "bf16-bs8", "fp8-bs16", "row64",
        "f16", "3d-rows"])
def test_kernel_engages_on_pool_shape_and_dtype(shape, dtype, engages):
    cfg = _cfg()
    assert PA.engages(jax.ShapeDtypeStruct(shape, dtype), cfg) is engages


def test_kernel_does_not_engage_on_mla_pools():
    """MLA's latent pools never engage, even at widths the kernel tiles,
    so a latent-attention decode step lowers to the same program with
    the kernel's rule in place as with it forced off."""
    cfg = dataclasses.replace(get_smoke_config("deepseek-v2-236b"),
                              kv_lora_rank=128)
    api = build_model(cfg)
    pools = api.paged_init(8, 16, 2)
    assert not api.paged_kernel(pools)
    assert not any(PA.engages(pool, cfg) for pool in pools.values())
    params = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    args = (params, pools, jnp.zeros((2, 1), jnp.int32),
            jnp.zeros((2, 4), jnp.int32), jnp.zeros((2,), jnp.int32),
            jnp.ones((2,), bool))

    def text():
        return jax.jit(api.paged_decode).lower(*args).as_text()

    plain = text()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(PA, "engages", lambda pool, cfg: False)
        assert text() == plain
    assert "paged_attention" not in plain


@pytest.mark.parametrize("arch", [
    "qwen1.5-0.5b", "olmoe-1b-7b", "deepseek-v2-236b", "codeqwen1.5-7b",
    "zamba2-1.2b"])
def test_smoke_configs_keep_the_masked_path(arch):
    """Rows of 64 values, MLA, or a family with its own decode: no smoke
    config's decode takes the kernel, so the bit-identity tests of the
    paged engine keep their meaning."""
    cfg = get_smoke_config(arch)
    api = build_model(cfg)
    pools = api.paged_init(8, 8, 2)
    assert not (api.paged_kernel and api.paged_kernel(pools))


def _served(cfg, params, lens, max_new=4):
    eng = PagedServeEngine(cfg, max_lanes=3, max_seq=SEQ, block_size=16)
    eng.load(params)
    rng = np.random.default_rng(9)
    seen = []
    for n in lens:
        eng.submit(rng.integers(0, cfg.vocab, n).astype(np.int32),
                   max_new_tokens=max_new)
    while eng.step():
        pass
    for r in eng.finished:
        # decode steps read pos = len(prompt) .. len(prompt)+max_new-2,
        # each pos + 1 rows
        seen.extend(range(len(r.prompt) + 1, len(r.prompt) + max_new))
    return eng, {r.rid: r.output for r in eng.finished}, sum(seen)


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "masked"])
def test_engine_counts_kernel_steps_and_rows_read(kernel, monkeypatch):
    """``decode_kernel_steps`` counts every decode step where the kernel
    runs and none where it does not; ``decode_kv_rows`` counts each
    active lane's ``pos + 1`` rows under the kernel, none without it.
    Both paths serve the same greedy tokens, and the decode program
    still compiles once."""
    cfg, params = _model()
    if not kernel:
        monkeypatch.setattr(PA, "engages", lambda pool, cfg: False)
    eng, out, rows = _served(cfg, params, (5, 17, 40))
    steps = eng.stats["steps"]
    assert steps > 0 and eng.stats["decode_compiles"] == 1
    if kernel:
        assert eng.stats["decode_kernel_steps"] == steps
        assert eng.stats["decode_kv_rows"] == rows
    else:
        assert eng.stats["decode_kernel_steps"] == 0
        assert eng.stats["decode_kv_rows"] == 0
    assert out == _kernel_free_outputs()


@functools.lru_cache(maxsize=None)
def _kernel_free_outputs():
    cfg, params = _model()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(PA, "engages", lambda pool, cfg: False)
        return _served(cfg, params, (5, 17, 40))[1]


def test_compiled_decode_step_carries_the_kernel_scope():
    """The kernel's ops sit under ``attn.paged_attention`` in the decode
    program, and no gather or masked bmm is left in it."""
    cfg, params = _model()
    eng = PagedServeEngine(cfg, max_lanes=2, max_seq=SEQ, block_size=16)
    eng.load(params)
    text = eng._decode_exec.as_text()
    assert "/attn.paged_attention/" in text
    for gone in ("/kv.gather/", "/attn.paged_scores/",
                 "/attn.paged_values/"):
        assert gone not in text, gone


def test_one_decode_program_across_every_bucket_up_to_max_seq():
    """Prompts in every prefill bucket, the longest filling the horizon,
    so that decode runs at positions from the first block to the last
    row a request may write (``max_seq - 2``; ``validate_request`` keeps
    one row for the last token): the engine builds one decode program at
    load, and once each bucket's prefill has been seen JAX compiles
    nothing more (no kernel variant by position, bucket or lane
    count)."""
    cfg, params = _model()
    buckets = (16, 32, 64, SEQ)
    eng = PagedServeEngine(
        cfg, max_lanes=3, max_seq=SEQ, block_size=16,
        scheduler=SchedulerConfig(prefill_buckets=buckets))
    eng.load(params)
    rng = np.random.default_rng(11)

    def serve(lens, max_new):
        for n in lens:
            eng.submit(rng.integers(0, cfg.vocab, n).astype(np.int32),
                       max_new_tokens=max_new)
        while eng.step():
            pass

    serve([b - 3 for b in buckets], 2)
    compiles = []

    def listen(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    steps = eng.stats["steps"]
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        serve([1, 16, 17, 33, 64, 65, SEQ - 2], 2)
        serve([SEQ - 10], 10)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert eng.stats["steps"] > steps
    assert eng.stats["decode_compiles"] == 1
    assert eng.stats["prefill_compiles"] == len(buckets)
    assert eng.stats["decode_kernel_steps"] == eng.stats["steps"]
    assert compiles == []
