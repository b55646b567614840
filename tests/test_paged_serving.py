"""Continuous-batching serving: block-paged KV cache, scheduler, and the
slot-engine bugs the new engine flushed out.

The three regression tests at the top (`test_max_new_tokens_one_*`,
`test_submit_rejects_*`, `test_plan_report_*`) are written against
``ServeEngine`` only and fail on the pre-paged engine — they pin the
bugfixes, not the new subsystem."""

import dataclasses
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.core.mapper import plan_cache_info
from repro.models import build_model
from repro.serve import (BlockAllocator, PagedServeEngine, Scheduler,
                         SchedulerConfig, ServeEngine)


@functools.lru_cache(maxsize=None)
def _setup(arch="qwen1.5-0.5b", kv_dtype=None):
    cfg = get_smoke_config(arch)
    if kv_dtype:
        cfg = dataclasses.replace(cfg, kv_cache_dtype=kv_dtype)
    api = build_model(cfg)
    return cfg, api.init(jax.random.PRNGKey(42))


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, n).astype(np.int32) for n in lens]


def _drain(eng, prompts, max_new=5, extras=None):
    for i, p in enumerate(prompts):
        eng.submit(p, max_new_tokens=max_new,
                   extra=(extras[i] if extras else None))
    return {r.rid: r.output for r in eng.run_until_drained(4000)}


def _slot(cfg, params, **kw):
    eng = ServeEngine(cfg, **kw)
    eng.load(params)
    return eng


def _paged(cfg, params, **kw):
    eng = PagedServeEngine(cfg, **kw)
    eng.load(params)
    return eng


# ---------------------------------------------------------------------------
# slot-engine regressions (fail on the pre-paged engine)
# ---------------------------------------------------------------------------

def test_max_new_tokens_one_emits_exactly_one_token():
    """A max_new_tokens=1 request is satisfied by the prefill token; the
    old engine still parked it in a lane and ran a decode step, emitting
    a second token past the budget."""
    cfg, params = _setup()
    eng = _slot(cfg, params, max_slots=2, max_seq=32)
    rid = eng.submit(_prompts(cfg, [6])[0], max_new_tokens=1)
    done = eng.run_until_drained()
    assert [r.rid for r in done] == [rid]
    assert len(done[0].output) == 1
    # and it never occupied a lane: a follow-up request is unaffected
    assert eng.slots == [None, None]


def test_submit_rejects_requests_past_the_sequence_horizon():
    """prompt + max_new_tokens > max_seq used to be accepted; the decode
    write then silently clamped at the horizon, overwriting the last
    cache row in place (token soup, no error)."""
    cfg, params = _setup()
    eng = _slot(cfg, params, max_slots=1, max_seq=32)
    with pytest.raises(ValueError, match="max_seq"):
        eng.submit(_prompts(cfg, [20])[0], max_new_tokens=20)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(_prompts(cfg, [4])[0], max_new_tokens=0)
    # boundary: exactly max_seq rows is servable
    eng.submit(_prompts(cfg, [20])[0], max_new_tokens=12)
    done = eng.run_until_drained()
    assert len(done) == 1 and len(done[0].output) == 12


def test_plan_report_deltas_every_counter():
    """plan_report must be a true delta of the warmup window.  The old
    load() delta'd only planned/fallback and copied backends/shapes
    cumulatively, so a second engine's report double-counted the first
    engine's warmup traffic."""
    cfg, params = _setup()
    r1 = _slot(cfg, params, max_slots=2, max_seq=32).plan_report
    r2 = _slot(cfg, params, max_slots=2, max_seq=32).plan_report
    assert set(r1) == set(r2)
    for site in r1:
        assert r1[site]["backends"] == r2[site]["backends"], site
        assert r1[site].get("shapes") == r2[site].get("shapes"), site


# ---------------------------------------------------------------------------
# allocator / scheduler units
# ---------------------------------------------------------------------------

def test_block_allocator_alloc_release_exhaustion():
    a = BlockAllocator(4)
    b1 = a.alloc(3)
    assert a.free == 1 and len(b1) == 3
    with pytest.raises(MemoryError, match="exhausted"):
        a.alloc(2)
    a.release(b1[:2])
    assert a.free == 3
    assert len(a.alloc(3)) == 3 and a.free == 0


def test_scheduler_buckets_and_exact_mode():
    s = Scheduler()
    assert s.bucket_for(5) == 8
    assert s.bucket_for(8) == 8
    assert s.bucket_for(9) == 16
    assert s.bucket_for(1000) == 1000  # past the last bucket: exact
    assert s.bucket_for(5, exact=True) == 5
    assert Scheduler(SchedulerConfig(bucketed=False)).bucket_for(5) == 5


def test_scheduler_admission_budget_and_fcfs():
    s = Scheduler(SchedulerConfig(max_prefills_per_step=2))
    # cold engine: every free lane fills at once
    assert s.plan_admits([1, 1, 1, 1], free_lanes=4, free_blocks=8,
                         n_active=0) == 4
    # in-flight decodes: at most max_prefills_per_step join
    assert s.plan_admits([1, 1, 1], free_lanes=3, free_blocks=8,
                         n_active=1) == 2
    # FCFS stops at the first request that does not fit (no starvation)
    assert s.plan_admits([5, 1], free_lanes=2, free_blocks=4,
                         n_active=0) == 0
    assert s.plan_admits([], free_lanes=2, free_blocks=4, n_active=0) == 0


def test_paged_cache_rejects_unaligned_horizon():
    cfg, params = _setup()
    with pytest.raises(ValueError, match="multiple"):
        _paged(cfg, params, max_lanes=1, max_seq=30, block_size=8)


# ---------------------------------------------------------------------------
# paged vs slot: bit-identical outputs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lanes", [1, 4])
def test_paged_matches_slot_bit_identical(lanes):
    cfg, params = _setup()
    prompts = _prompts(cfg, [5, 9, 13, 4, 17, 7], seed=3)
    ref = _drain(_slot(cfg, params, max_slots=lanes, max_seq=64), prompts)
    got = _drain(_paged(cfg, params, max_lanes=lanes, max_seq=64,
                        block_size=8), prompts)
    assert ref == got


@pytest.mark.parametrize("arch,lanes", [
    ("deepseek-v2-236b", 1),   # MoE + MLA: absorbed paged decode
    ("mamba2-780m", 2),        # pure SSM: lane-resident state only
    ("olmoe-1b-7b", 2),        # MoE with GQA: MoE layers only, no dense
])
def test_paged_matches_slot_across_families(arch, lanes):
    cfg, params = _setup(arch)
    prompts = _prompts(cfg, [5, 9, 7], seed=1)
    ref = _drain(_slot(cfg, params, max_slots=lanes, max_seq=64), prompts)
    got = _drain(_paged(cfg, params, max_lanes=lanes, max_seq=64,
                        block_size=8), prompts)
    assert ref == got


def _decode_write_then_gather(p, cfg, pools, tokens, tables, pos, active):
    """The decode step as every layer once ran it: per-layer pools
    through the layer scan, each layer writing its row with
    ``paged_write`` and then reading its lanes with ``paged_gather``."""
    from repro.models import layers as L
    from repro.models import transformer as TFM

    heads = (cfg.n_kv_heads, cfg.hd)

    def body(x, inp):
        lp, pk, pv = inp
        h = L.apply_norm(lp["ln1"], cfg, x)
        attn, pk, pv = L.apply_attention_decode_paged(
            lp["attn"], cfg, h, pk, pv, tables, pos, active)
        x = x + attn
        h = L.apply_norm(lp["ln2"], cfg, x)
        return x + L.apply_mlp(lp["mlp"], cfg, h), (pk, pv)

    split = [pools[k].reshape(*pools[k].shape[:3], *heads) for k in "kv"]
    x = TFM.embed_tokens(p, cfg, tokens)
    x, (pk, pv) = jax.lax.scan(body, x, (p["dense_layers"], *split),
                               unroll=cfg.scan_unroll)
    x = L.apply_norm(p["ln_f"], cfg, x)
    logits = TFM.logits_fn(p, cfg, x)[:, 0]
    return logits, {"k": pk.reshape(pools["k"].shape),
                    "v": pv.reshape(pools["v"].shape)}


def test_decode_reads_pools_then_writes_rows_once_as_write_then_gather():
    """The decode step gathers from the pools it was given, puts each
    lane's new row into its sequence, and writes every layer's rows
    after the layer scan: the same pools and active-lane logits, bit for
    bit, as writing each row before gathering, layer by layer.  Lane 3
    is inactive and its table points at lane 0's blocks: it writes
    nothing."""
    cfg, params = _setup()
    api = build_model(cfg)
    nb, bs, lanes, per_lane = 24, 4, 4, 6
    pools = api.paged_init(nb, bs, lanes)
    keys = jax.random.split(jax.random.PRNGKey(7), len(pools))
    pools = {k: jax.random.normal(kk, v.shape, jnp.float32).astype(v.dtype)
             for kk, (k, v) in zip(keys, sorted(pools.items()))}
    rng = np.random.default_rng(11)
    tables = rng.permutation(nb)[:lanes * per_lane].reshape(lanes, per_lane)
    tables[3] = tables[0]
    tables = jnp.asarray(tables, jnp.int32)
    pos = jnp.asarray([13, 0, 23, 6], jnp.int32)
    active = jnp.asarray([True, True, True, False])
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (lanes, 1)), jnp.int32)

    ref_logits, ref_pools = jax.jit(
        lambda *a: _decode_write_then_gather(params, cfg, *a))(
            pools, tokens, tables, pos, active)
    logits, new_pools = jax.jit(
        lambda *a: api.paged_decode(params, *a))(
            pools, tokens, tables, pos, active)

    for k in pools:
        np.testing.assert_array_equal(np.asarray(new_pools[k], np.float32),
                                      np.asarray(ref_pools[k], np.float32))
        # lane 3's row would land in lane 0's block 1; only the active
        # lanes' rows changed
        changed = np.argwhere(np.any(
            np.asarray(new_pools[k] != pools[k]), axis=-1))
        want = {(layer, int(tables[i, pos[i] // bs]), int(pos[i]) % bs)
                for layer in range(cfg.n_layers) for i in range(3)}
        assert {tuple(map(int, c)) for c in changed} == want, k
    np.testing.assert_array_equal(np.asarray(logits[:3]),
                                  np.asarray(ref_logits[:3]))


def test_decode_step_donates_the_pools():
    """The engine donates the block pools to the decode executable, so
    each step updates them in place: the step's input pools are gone
    after it, and the engine holds the live output."""
    cfg, params = _setup()
    eng = _paged(cfg, params, max_lanes=2, max_seq=32, block_size=8)
    eng.submit(_prompts(cfg, [6])[0], max_new_tokens=4)
    eng.step()                  # admission, then the first decode
    before = eng.kv.pools
    eng.step()                  # a decode-only step
    assert all(a.is_deleted() for a in before.values())
    assert not any(a.is_deleted() for a in eng.kv.pools.values())
    assert len(eng.run_until_drained()[0].output) == 4


def test_bucketed_prefill_is_output_transparent():
    """Bucket pad tokens must be invisible: same outputs as exact-length
    prefill (the masked-attention guarantee the scheduler relies on)."""
    cfg, params = _setup()
    prompts = _prompts(cfg, [5, 9, 13], seed=5)
    exact = _drain(
        _paged(cfg, params, max_lanes=2, max_seq=64, block_size=8,
               scheduler=Scheduler(SchedulerConfig(bucketed=False))),
        prompts)
    bucketed = _drain(
        _paged(cfg, params, max_lanes=2, max_seq=64, block_size=8),
        prompts)
    assert exact == bucketed


def test_fp8_cache_roundtrips_through_paged_pools():
    cfg, params = _setup(kv_dtype="float8_e4m3fn")
    prompts = _prompts(cfg, [5, 9, 7], seed=2)
    ref = _drain(_slot(cfg, params, max_slots=2, max_seq=64), prompts)
    got = _drain(_paged(cfg, params, max_lanes=2, max_seq=64,
                        block_size=8), prompts)
    assert ref == got


def test_write_prefill_rejects_mismatched_dtype():
    cfg, params = _setup()
    eng = _paged(cfg, params, max_lanes=2, max_seq=32, block_size=8)
    batch = {"tokens": jnp.asarray(_prompts(cfg, [8])[0][None])}
    _, pc = eng.api.prefill(eng.params, batch, 8,
                            last_index=jnp.asarray([7], jnp.int32))
    bad = {k: (v.astype(jnp.float16)
               if jnp.issubdtype(v.dtype, jnp.floating) else v)
           for k, v in pc.items()}
    eng.kv.install_lane(0, eng.kv.allocator.alloc(1), 8)
    with pytest.raises(TypeError, match="dtype"):
        eng.kv.write_prefill(0, bad)


# ---------------------------------------------------------------------------
# zero-recompile continuous batching
# ---------------------------------------------------------------------------

def test_join_evict_mid_flight_never_recompiles_decode():
    """Requests joining and finishing mid-flight edit host tables only:
    the AOT decode executable is compiled exactly once in load() and the
    very same object serves every step."""
    cfg, params = _setup()
    eng = _paged(cfg, params, max_lanes=4, max_seq=64, block_size=8)
    assert eng.stats["decode_compiles"] == 1
    exec_id = id(eng._decode_exec)
    prompts = _prompts(cfg, [6, 11, 6, 6, 9, 6], seed=7)
    for p in prompts[:3]:
        eng.submit(p, max_new_tokens=6)
    for _ in range(4):          # some finish, lanes evict
        eng.step()
    for p in prompts[3:]:       # late joins into freed lanes
        eng.submit(p, max_new_tokens=4)
    done = eng.run_until_drained(1000)
    assert len(done) == 6
    assert eng.stats["decode_compiles"] == 1
    assert id(eng._decode_exec) == exec_id


def test_steady_state_zero_plan_cache_misses():
    """After the first drain warms every bucket, repeat traffic must hit
    the plan LRU on every lookup and never touch the autotune table's
    measurement path."""
    from repro.core import autotune

    cfg, params = _setup()
    eng = _paged(cfg, params, max_lanes=2, max_seq=64, block_size=8)
    _drain(eng, _prompts(cfg, [5, 9], seed=1), max_new=3)
    misses = plan_cache_info().misses
    measures = autotune.counters()["measure_calls"]
    prefills = eng.stats["prefill_compiles"]
    _drain(eng, _prompts(cfg, [6, 12], seed=2), max_new=3)  # same buckets
    assert plan_cache_info().misses == misses
    assert autotune.counters()["measure_calls"] == measures
    assert eng.stats["prefill_compiles"] == prefills


# ---------------------------------------------------------------------------
# block pool pressure: growth, preemption, guard
# ---------------------------------------------------------------------------

def test_preemption_under_block_pressure_preserves_outputs():
    """An oversubscribed pool forces a mid-flight eviction; the victim
    re-queues with its generated tokens folded into the prompt and its
    final output is unchanged (greedy decode is recompute-transparent)."""
    cfg, params = _setup()
    prompts = _prompts(cfg, [20, 20, 20, 20], seed=4)
    ref = _drain(_slot(cfg, params, max_slots=4, max_seq=64), prompts,
                 max_new=20)
    eng = _paged(cfg, params, max_lanes=4, max_seq=64, block_size=8,
                 num_blocks=14)   # 4 lanes x 40 rows need 20 blocks
    got = _drain(eng, prompts, max_new=20)
    assert eng.stats["preemptions"] > 0
    assert eng.stats["decode_compiles"] == 1
    assert ref == got


def test_guard_refuses_decode_write_past_horizon():
    cfg, params = _setup()
    eng = _paged(cfg, params, max_lanes=1, max_seq=32, block_size=8)
    eng.submit(_prompts(cfg, [6])[0], max_new_tokens=4)
    eng.step()
    eng.kv.pos[0] = 32          # corrupt: next write would clamp
    with pytest.raises(AssertionError, match="horizon"):
        eng.kv.guard_decode_write()
    eng.kv.pos[0] = 30          # past the lane's allocated blocks
    with pytest.raises(AssertionError, match="blocks"):
        eng.kv.guard_decode_write()


def test_paged_submit_validates_horizon():
    cfg, params = _setup()
    eng = _paged(cfg, params, max_lanes=1, max_seq=32, block_size=8)
    with pytest.raises(ValueError, match="max_seq"):
        eng.submit(_prompts(cfg, [20])[0], max_new_tokens=20)
