"""Substrate tests: optimizer, checkpoint, data, MoE routing, serving."""

import os
import tempfile

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import ShapeSpec
from repro.optim import adamw_init, adamw_update, cosine_schedule, global_norm


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def test_adamw_decreases_quadratic():
    params = {"w": jnp.asarray([5.0, -3.0])}
    state = adamw_init(params)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}
        params, state, m = adamw_update(
            grads, state, params, lr=0.1, weight_decay=0.0)
    assert float(jnp.abs(params["w"]).max()) < 0.5
    assert int(state.count) == 200


def test_adamw_clipping():
    params = {"w": jnp.ones(4)}
    state = adamw_init(params)
    grads = {"w": jnp.full(4, 1e6)}
    _, _, metrics = adamw_update(grads, state, params, lr=0.1,
                                 clip_norm=1.0)
    assert float(metrics["clip_scale"]) < 1e-5


def test_cosine_schedule_shape():
    s0 = cosine_schedule(jnp.asarray(0), base_lr=1.0, warmup=10, total=100)
    s10 = cosine_schedule(jnp.asarray(10), base_lr=1.0, warmup=10,
                          total=100)
    s100 = cosine_schedule(jnp.asarray(100), base_lr=1.0, warmup=10,
                           total=100)
    assert float(s0) == 0.0
    assert abs(float(s10) - 1.0) < 1e-6
    assert float(s100) == pytest.approx(0.1, abs=1e-6)


def test_global_norm():
    t = {"a": jnp.asarray([3.0]), "b": jnp.asarray([4.0])}
    assert float(global_norm(t)) == pytest.approx(5.0)


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip():
    from repro.ckpt import latest_step, restore_checkpoint, save_checkpoint

    tree = {"a": jnp.arange(6).reshape(2, 3),
            "b": {"c": jnp.ones(4, jnp.bfloat16)}}
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, 7, tree)
        assert latest_step(d) == 7
        out = restore_checkpoint(d, 7, tree)
        np.testing.assert_array_equal(out["a"], tree["a"])
        np.testing.assert_array_equal(
            np.asarray(out["b"]["c"], np.float32),
            np.asarray(tree["b"]["c"], np.float32))


def test_checkpoint_atomic_no_partial():
    from repro.ckpt import latest_step, save_checkpoint

    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, 1, {"x": jnp.zeros(2)})
        # a .tmp dir must never count as a checkpoint
        os.makedirs(os.path.join(d, "step_00000009.tmp"))
        assert latest_step(d) == 1


def test_checkpoint_shape_mismatch_rejected():
    from repro.ckpt import restore_checkpoint, save_checkpoint

    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, 1, {"x": jnp.zeros(2)})
        with pytest.raises(ValueError):
            restore_checkpoint(d, 1, {"x": jnp.zeros(3)})


def test_async_checkpointer_gc():
    from repro.ckpt import AsyncCheckpointer, latest_step

    with tempfile.TemporaryDirectory() as d:
        ck = AsyncCheckpointer(d, keep=2)
        for s in (1, 2, 3, 4):
            ck.save(s, {"x": jnp.full(2, s)})
        ck.close()
        steps = sorted(
            int(p.split("_")[1]) for p in os.listdir(d)
            if p.startswith("step_"))
        assert steps == [3, 4]
        assert latest_step(d) == 4


# ---------------------------------------------------------------------------
# MoE routing invariants
# ---------------------------------------------------------------------------

def test_moe_routing_topk_weights_normalized():
    from repro.models.moe import route

    cfg = get_smoke_config("olmoe-1b-7b")
    logits = jnp.asarray(
        np.random.default_rng(0).standard_normal((32, cfg.moe_num_experts)))
    w, ids, probs = route(cfg, logits)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, atol=1e-5)
    assert ids.shape == (32, cfg.moe_top_k)
    # ids are the true top-k of probs
    expect = np.argsort(-np.asarray(probs), axis=-1)[:, : cfg.moe_top_k]
    assert np.array_equal(np.sort(np.asarray(ids), -1), np.sort(expect, -1))


def test_moe_dispatch_respects_capacity():
    from repro.models.moe import _dispatch_indices

    cfg = get_smoke_config("olmoe-1b-7b")
    rng = np.random.default_rng(1)
    ids = jnp.asarray(
        rng.integers(0, cfg.moe_num_experts, (64, cfg.moe_top_k)))
    cap = 4
    order, slot, keep, token = _dispatch_indices(cfg, ids, cap)
    # no slot is used twice among kept assignments
    kept_slots = np.asarray(slot)[np.asarray(keep)]
    assert len(set(kept_slots.tolist())) == len(kept_slots)
    assert kept_slots.max() < cfg.moe_num_experts * cap


def test_moe_tp_equals_dense_when_single_shard():
    """moe_ffn_tokens with local_experts covering everything == without."""
    from repro.models.moe import init_moe, moe_ffn_tokens

    cfg = get_smoke_config("olmoe-1b-7b")
    p = init_moe(jax.random.PRNGKey(0), cfg)
    x = jnp.asarray(
        np.random.default_rng(2).standard_normal((32, cfg.d_model)),
        jnp.float32)
    y1, _, _ = moe_ffn_tokens(cfg, p, x)
    y2, _, _ = moe_ffn_tokens(cfg, p, x,
                           local_experts=(0, cfg.moe_num_experts))
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5)


# ---------------------------------------------------------------------------
# serving engine
# ---------------------------------------------------------------------------

def test_serve_engine_continuous_batching():
    from repro.serve import make_engine

    cfg = get_smoke_config("qwen1.5-0.5b")
    from repro.models import build_model
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    eng = make_engine(cfg, max_lanes=2, max_seq=32, block_size=8)
    eng.load(params)
    rng = np.random.default_rng(3)
    rids = [eng.submit(rng.integers(0, cfg.vocab, 5), max_new_tokens=4)
            for _ in range(5)]
    done = eng.run_until_drained()
    assert len(done) == 5
    assert all(len(r.output) == 4 for r in done)
    assert sorted(r.rid for r in done) == sorted(rids)


def test_serve_deterministic_per_request():
    """Lane placement must not change a request's outputs."""
    from repro.serve import make_engine
    from repro.models import build_model

    cfg = get_smoke_config("qwen1.5-0.5b")
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    prompt = np.arange(6) % cfg.vocab

    outs = []
    for lanes in (1, 3):
        eng = make_engine(cfg, max_lanes=lanes, max_seq=32, block_size=8)
        eng.load(params)
        eng.submit(prompt, max_new_tokens=5)
        done = eng.run_until_drained()
        outs.append(done[0].output)
    assert outs[0] == outs[1]


# ---------------------------------------------------------------------------
# trainer fault tolerance
# ---------------------------------------------------------------------------

def test_trainer_checkpoint_resume_exact():
    from repro.train import Trainer, TrainConfig

    cfg = get_smoke_config("qwen1.5-0.5b")
    shape = ShapeSpec("t", "train", 32, 4)
    with tempfile.TemporaryDirectory() as d:
        tc = TrainConfig(ckpt_every=4, log_every=100, total_steps=50,
                         base_lr=1e-3)
        t1 = Trainer(cfg, shape, ckpt_dir=d, tcfg=tc)
        p1, _, h1 = t1.run(8, resume=False)
        # fresh trainer resumes from step 8 and must see the same data
        t2 = Trainer(cfg, shape, ckpt_dir=d, tcfg=tc)
        p2, _, h2 = t2.run(2, resume=True)
        # parameters diverge only by the 2 extra steps, not by data skew
        t3 = Trainer(cfg, shape, ckpt_dir=d, tcfg=tc)
        # no checkpoints removed; latest is 10 now
        from repro.ckpt import latest_step
        assert latest_step(d) == 10
