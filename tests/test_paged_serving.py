"""Continuous-batching serving: block-paged KV cache, scheduler, and the
engine's outputs against the plain per-request reference
(``serve_reference.greedy_reference``: the model's contiguous decode,
one request at a time)."""

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
                         SchedulerConfig, synth_samples)
from serve_reference import greedy_reference


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


def _reference(cfg, params, prompts, max_seq, max_new=5):
    """``_drain``'s outputs as the plain reference gives them."""
    api = build_model(cfg)
    return {i: greedy_reference(api, params, p, max_new, max_seq)
            for i, p in enumerate(prompts)}


def _paged(cfg, params, **kw):
    eng = PagedServeEngine(cfg, **kw)
    eng.load(params)
    return eng


# ---------------------------------------------------------------------------
# request budget, horizon and plan-report regressions
# ---------------------------------------------------------------------------

def test_max_new_tokens_one_emits_exactly_one_token():
    """A max_new_tokens=1 request is satisfied by the prefill token: it
    finishes at admission, never parked in a lane for a decode step
    that would emit a second token past the budget."""
    cfg, params = _setup()
    eng = _paged(cfg, params, max_lanes=2, max_seq=32, block_size=8)
    rid = eng.submit(_prompts(cfg, [6])[0], max_new_tokens=1)
    done = eng.run_until_drained()
    assert [r.rid for r in done] == [rid]
    assert len(done[0].output) == 1
    # and it never occupied a lane: a follow-up request is unaffected
    assert eng.lanes == [None, None]
    assert eng.stats["steps"] == 0


def test_submit_rejects_requests_past_the_sequence_horizon():
    """prompt + max_new_tokens > max_seq is refused at submit time: the
    decode write has no cache row past the horizon."""
    cfg, params = _setup()
    eng = _paged(cfg, params, max_lanes=1, max_seq=32, block_size=8)
    with pytest.raises(ValueError, match="max_seq"):
        eng.submit(_prompts(cfg, [20])[0], max_new_tokens=20)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(_prompts(cfg, [4])[0], max_new_tokens=0)
    # boundary: exactly max_seq rows is servable
    eng.submit(_prompts(cfg, [20])[0], max_new_tokens=12)
    done = eng.run_until_drained()
    assert len(done) == 1 and len(done[0].output) == 12


def test_plan_report_deltas_every_counter():
    """plan_report must be a true delta of the warmup window, every
    counter included: a delta of planned/fallback alone, with backends
    and shapes copied cumulatively, would make a second engine's report
    double-count the first engine's warmup traffic."""
    cfg, params = _setup()
    r1 = _paged(cfg, params, max_lanes=2, max_seq=32).plan_report
    r2 = _paged(cfg, params, max_lanes=2, max_seq=32).plan_report
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
# the engine vs the per-request contiguous reference: identical outputs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lanes", [1, 4])
def test_paged_matches_slot_bit_identical(lanes):
    """Batched paged decode gives each request exactly the tokens of its
    own contiguous cache, decoded alone."""
    cfg, params = _setup()
    prompts = _prompts(cfg, [5, 9, 13, 4, 17, 7], seed=3)
    ref = _reference(cfg, params, prompts, 64)
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
    ref = _reference(cfg, params, prompts, 64)
    got = _drain(_paged(cfg, params, max_lanes=lanes, max_seq=64,
                        block_size=8), prompts)
    assert ref == got


def _decode_write_then_gather(api, p, pools, tokens, tables, pos, active):
    """The decode step as a contiguous cache runs it: each lane's rows
    gathered from its blocks into a [L, B, S, ...] cache, the family's
    ``decode`` writing every layer's new row at ``pos`` before it
    attends over it, then each layer's new row stored into its pool
    layer by layer (an inactive lane's index out of range, dropped)."""
    b, t = tables.shape
    layout = api.paged_layout()
    rows_of = jax.eval_shape(lambda: api.init_cache(b, 1))
    cache = {"pos": pos}
    for name, kind in layout.items():
        pool = pools[name]
        if kind == "paged":
            g = pool[:, tables]                     # [L, B, T, bs, row]
            cache[name] = g.reshape(g.shape[0], b, -1,
                                    *rows_of[name].shape[3:])
        else:
            cache[name] = pool
    logits, new_cache = api.decode(p, cache, tokens)

    lanes = jnp.arange(b)
    new_pools = {}
    for name, kind in layout.items():
        pool = pools[name]
        if kind != "paged":
            new_pools[name] = new_cache[name]
            continue
        nl, nb, bs = pool.shape[:3]
        rows = new_cache[name][:, lanes, pos].reshape(nl, b, *pool.shape[3:])
        idx = tables[lanes, pos // bs] * bs + pos % bs
        idx = jnp.where(active, idx, nb * bs)
        for layer in range(nl):
            flat = pool[layer].reshape(nb * bs, *pool.shape[3:])
            flat = flat.at[idx].set(rows[layer], mode="drop")
            pool = pool.at[layer].set(flat.reshape(pool.shape[1:]))
        new_pools[name] = pool
    return logits, new_pools


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "zamba2-1.2b",
                                  "whisper-base"])
def test_decode_reads_pools_then_writes_rows_once_as_write_then_gather(arch):
    """The decode step gathers from the pools it was given, puts each
    lane's new row into its sequence, and writes every layer's rows
    after the layers: the same pools and active-lane logits, bit for
    bit, as writing each row before gathering, layer by layer.  Lane 3
    is inactive and its table points at lane 0's blocks: it writes
    nothing."""
    cfg, params = _setup(arch)
    api = build_model(cfg)
    nb, bs, lanes, per_lane = 24, 4, 4, 6
    pools = api.paged_init(nb, bs, lanes)
    keys = jax.random.split(jax.random.PRNGKey(7), len(pools))
    pools = {k: jax.random.normal(kk, v.shape, jnp.float32).astype(v.dtype)
             for kk, (k, v) in zip(keys, sorted(pools.items()))}
    if "enc_len" in pools:      # encoder frames each lane has seen
        pools["enc_len"] = jnp.asarray([32, 8, 16, 32], jnp.int32)
    rng = np.random.default_rng(11)
    tables = rng.permutation(nb)[:lanes * per_lane].reshape(lanes, per_lane)
    tables[3] = tables[0]
    tables = jnp.asarray(tables, jnp.int32)
    pos = jnp.asarray([13, 0, 23, 6], jnp.int32)
    active = jnp.asarray([True, True, True, False])
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (lanes, 1)), jnp.int32)

    ref_logits, ref_pools = jax.jit(
        lambda *a: _decode_write_then_gather(api, params, *a))(
            pools, tokens, tables, pos, active)
    logits, new_pools = jax.jit(
        lambda *a: api.paged_decode(params, *a))(
            pools, tokens, tables, pos, active)

    for k, kind in api.paged_layout().items():
        np.testing.assert_array_equal(np.asarray(new_pools[k], np.float32),
                                      np.asarray(ref_pools[k], np.float32))
        if kind != "paged":
            continue
        # lane 3's row would land in lane 0's block 1; only the active
        # lanes' rows changed
        changed = np.argwhere(np.any(
            np.asarray(new_pools[k] != pools[k]),
            axis=tuple(range(3, pools[k].ndim))))
        want = {(layer, int(tables[i, pos[i] // bs]), int(pos[i]) % bs)
                for layer in range(pools[k].shape[0]) for i in range(3)}
        assert {tuple(map(int, c)) for c in changed} == want, k
    np.testing.assert_array_equal(np.asarray(logits[:3]),
                                  np.asarray(ref_logits[:3]))


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "zamba2-1.2b",
                                  "whisper-base"])
def test_decode_step_donates_the_pools(arch):
    """The engine donates the block pools to the decode executable, so
    each step updates them in place: the step's input pools are gone
    after it, and the engine holds the live output."""
    cfg, params = _setup(arch)
    eng = _paged(cfg, params, max_lanes=2, max_seq=32, block_size=8)
    if eng.frontend is None:
        eng.submit(_prompts(cfg, [6])[0], max_new_tokens=4)
    else:   # one chunk: admission feeds it, no later step replaces a pool
        eng.submit_audio_stream(synth_samples(eng.frontend.cfg, 1, seed=0),
                                max_new_tokens=4)
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
    ref = _reference(cfg, params, prompts, 64)
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
    ref = _reference(cfg, params, prompts, 64, max_new=20)
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
