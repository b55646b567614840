"""Direct coverage for serve/engine.py: continuous batching semantics
and plan-once-serve-many (no plan-cache growth after warmup)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.core.mapper import plan_cache_info
from repro.models import build_model
from repro.serve import make_engine


def _engine(max_lanes=4, max_seq=64, arch="qwen1.5-0.5b", **kw):
    cfg = get_smoke_config(arch)
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(42))
    eng = make_engine(cfg, max_lanes=max_lanes, max_seq=max_seq,
                      block_size=8, **kw)
    eng.load(params)
    return cfg, eng


def _prompts(cfg, n, plen=6, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, plen) for _ in range(n)]


# ---------------------------------------------------------------------------
# continuous batching semantics
# ---------------------------------------------------------------------------

def test_admit_fills_free_lanes_and_queues_the_rest():
    cfg, eng = _engine(max_lanes=2)
    for p in _prompts(cfg, 5):
        eng.submit(p, max_new_tokens=4)
    eng._admit()
    assert sum(r is not None for r in eng.lanes) == 2
    assert len(eng.queue) == 3


def test_finished_lane_frees_and_next_request_joins():
    cfg, eng = _engine(max_lanes=1)
    r0, r1 = [eng.submit(p, max_new_tokens=2) for p in _prompts(cfg, 2)]
    # step 1: r0 admitted (prefill emits token 1), decode emits token 2 ->
    # r0 done, lane freed with r1 still queued
    remaining = eng.step()
    assert [r.rid for r in eng.finished] == [r0]
    assert remaining == 1  # r1 waiting
    eng.step()
    assert [r.rid for r in eng.finished] == [r0, r1]
    assert eng.lanes == [None]


def test_queue_drains_all_requests():
    cfg, eng = _engine(max_lanes=4)
    rids = [eng.submit(p, max_new_tokens=5)
            for p in _prompts(cfg, 7, plen=5)]
    done = eng.run_until_drained()
    assert sorted(r.rid for r in done) == sorted(rids)
    assert all(len(r.output) == 5 for r in done)
    assert eng.lanes == [None] * 4 and eng.queue == []


def test_run_until_drained_respects_max_steps():
    cfg, eng = _engine(max_lanes=1)
    for p in _prompts(cfg, 2):
        eng.submit(p, max_new_tokens=8)
    done = eng.run_until_drained(max_steps=3)
    # 3 steps of a 1-lane engine cannot finish 2x8 tokens — the bound
    # must return control instead of spinning
    assert len(done) < 2
    assert eng.queue or any(r is not None for r in eng.lanes)


@pytest.mark.parametrize("slots", [2, 4])
def test_outputs_identical_max_slots_1_vs_n(slots):
    # lanes=2 equals the smoke config's n_layers: a lane write that
    # told the layer axis from the lane axis by size would corrupt lanes
    cfg1, eng1 = _engine(max_lanes=1)
    cfgn, engn = _engine(max_lanes=slots)
    prompts = _prompts(cfg1, 5, plen=7, seed=3)
    for eng in (eng1, engn):
        for p in prompts:
            eng.submit(p, max_new_tokens=6)
    out1 = {r.rid: r.output for r in eng1.run_until_drained()}
    outn = {r.rid: r.output for r in engn.run_until_drained()}
    assert out1 == outn


def test_late_submissions_join_without_restart():
    cfg, eng = _engine(max_lanes=2)
    for p in _prompts(cfg, 2):
        eng.submit(p, max_new_tokens=6)
    eng.step()
    eng.step()
    late = eng.submit(_prompts(cfg, 1, seed=9)[0], max_new_tokens=3)
    done = eng.run_until_drained()
    assert late in {r.rid for r in done}


# ---------------------------------------------------------------------------
# plan-once-serve-many
# ---------------------------------------------------------------------------

def test_load_plans_and_compiles_decode_ahead():
    cfg, eng = _engine(max_lanes=2, prompt_len=6)
    assert eng._decode_exec is not None
    # the warmup trace routed the serving GEMMs through the facade
    assert eng.plan_report, "load() must snapshot the planning report"
    planned_sites = [s for s, st in eng.plan_report.items()
                     if st["planned"] > 0]
    assert any(s.startswith("mlp.") for s in planned_sites)
    assert any(s.startswith("attn.") for s in planned_sites)


def test_load_prefill_warmup_covers_encdec_family():
    """The family-aware prefill spec must include the encoder frames —
    an encdec engine with prompt_len used to KeyError in load()."""
    cfg, eng = _engine(max_lanes=1, max_seq=32, arch="whisper-base",
                       prompt_len=4)
    assert eng._decode_exec is not None
    assert eng.plan_report


def test_plan_report_is_a_warmup_delta():
    """Traces that ran before load() must not leak into plan_report."""
    from repro.kernels import planned

    cfg = get_smoke_config("qwen1.5-0.5b")
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    # an unrelated training pass populates the global report with
    # forward/backward sites (attn.scores, */bwd_*)
    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab, (2, 8)), jnp.int32)
    jax.grad(lambda p: api.loss(p, {"tokens": toks, "labels": toks}))(
        params)
    assert any("/bwd_" in s for s in planned.planned_report())
    eng = make_engine(cfg, max_lanes=2, max_seq=32, block_size=8)
    eng.load(params)
    # decode-only warmup: no sdpa scores, no backward GEMMs
    assert not any("/bwd_" in s for s in eng.plan_report)
    assert "attn.scores" not in eng.plan_report
    assert "attn.paged_scores" in eng.plan_report


def test_engine_serves_with_planned_off():
    from repro.kernels import planned

    with planned.override(enabled=False):
        cfg, eng = _engine(max_lanes=2)
        assert all(st["planned"] == 0 for st in eng.plan_report.values())
        for p in _prompts(cfg, 2):
            eng.submit(p, max_new_tokens=3)
        done = eng.run_until_drained()
    assert len(done) == 2 and all(len(r.output) == 3 for r in done)


def test_steady_state_steps_do_not_grow_plan_cache():
    cfg, eng = _engine(max_lanes=2)
    # warmup: one full drain covers prefill + decode GEMM shapes
    for p in _prompts(cfg, 2, plen=6):
        eng.submit(p, max_new_tokens=3)
    eng.run_until_drained()
    misses = plan_cache_info().misses
    # steady state: same prompt length, more traffic -> every plan lookup
    # must hit the LRU cache (no per-step replanning)
    for p in _prompts(cfg, 4, plen=6, seed=1):
        eng.submit(p, max_new_tokens=3)
    eng.run_until_drained()
    assert plan_cache_info().misses == misses


def test_load_performs_no_measurement():
    """The serving acceptance pin: load() under the default cached
    policy reads the committed crossover table and *never* races
    backends — and steady-state traffic doesn't either."""
    from repro.core import autotune

    cfg, eng = _engine(max_lanes=2)
    assert eng.autotune_report["measure_calls"] == 0, eng.autotune_report
    before = autotune.counters()["measure_calls"]
    for p in _prompts(cfg, 2):
        eng.submit(p, max_new_tokens=3)
    eng.run_until_drained()
    assert autotune.counters()["measure_calls"] == before


def test_engine_accepts_explicit_policy():
    """A modelled-policy engine serves identically, with the table
    never consulted during its warmup."""
    from repro.core.autotune import PlanPolicy

    cfg, eng = _engine(max_lanes=2,
                       policy=PlanPolicy(mode="modelled"))
    assert eng.autotune_report["measure_calls"] == 0
    assert eng.autotune_report["hits"] == 0
    for p in _prompts(cfg, 2):
        eng.submit(p, max_new_tokens=3)
    done = eng.run_until_drained()
    assert len(done) == 2 and all(len(r.output) == 3 for r in done)
