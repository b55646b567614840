"""DeepSeek-V2-style serving at a small size: one chip's share of an
expert-parallel MoE layer, dropless routing that ignores pad rows and
inactive lanes, un-normalised top-k, and YaRN rope.

The plain forward below is numpy in float64, written from the published
``DeepseekV2ForCausalLM`` equations (expanded latent attention, softmax
router, greedy top-k, shared experts, one leading dense layer); it shares
nothing with the program but the parameter tree's key names.  The rope
rotates the halves of the rope dims, as the program does: the published
model rotates interleaved pairs, a fixed permutation of the rope columns
of ``wq`` and ``wkr`` that a checkpoint loader would apply."""

import dataclasses
import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.configs.base import ModelConfig
from repro.models import build_model
from repro.models import layers as L
from repro.models import moe as MOE
from repro.serve import PagedServeEngine, SchedulerConfig

#: MLA without q_lora, 1 dense + 3 MoE layers, a router of 16 experts of
#: which 4 are held, top-6 un-normalised, 2 shared experts, YaRN
TINY = ModelConfig(
    name="dsv2-tiny", family="moe", n_layers=4, d_model=64, n_heads=4,
    n_kv_heads=4, d_ff=96, vocab=128, norm_eps=1e-6, dtype="float32",
    kv_cache_dtype="float32", moe_num_experts=4, moe_router_experts=16,
    moe_top_k=6, moe_norm_topk=False, moe_shared_experts=2, moe_d_ff=16,
    moe_first_dense=1, use_mla=True, kv_lora_rank=32, q_lora_rank=0,
    rope_head_dim=16, nope_head_dim=16, v_head_dim=16,
    rope_scaling_factor=40.0, rope_original_positions=64,
    rope_beta_fast=32.0, rope_beta_slow=1.0, rope_mscale=0.707,
    rope_mscale_all_dim=0.707)


@pytest.fixture(scope="module")
def tiny():
    params = build_model(TINY).init(jax.random.PRNGKey(7))
    # a router of std 1 per logit, so that routing is not near-uniform
    params["moe_layers"]["moe"]["router"] = jax.random.normal(
        jax.random.PRNGKey(8), params["moe_layers"]["moe"]["router"].shape
    ) / math.sqrt(TINY.d_model)
    return params


# ---------------------------------------------------------------------------
# the plain forward
# ---------------------------------------------------------------------------

def _np(tree):
    return jax.tree.map(lambda a: np.asarray(a, np.float64), tree)


def _rms(x, w, eps):
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * w


def _silu(x):
    return x / (1.0 + np.exp(-x))


def _yarn_get_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def _yarn_inv_freq(cfg, dim):
    """``DeepseekV2YarnRotaryEmbedding``'s inverse frequencies."""
    base, f = cfg.rope_theta, cfg.rope_scaling_factor
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)

    def corr(rot):
        return (dim * math.log(cfg.rope_original_positions
                               / (rot * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(corr(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(corr(cfg.rope_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    return extra / f * (1 - mask) + extra * mask


def _rope(x, cfg):
    """x [S, ..., rope]: halves rotated at YaRN's frequencies."""
    dim = x.shape[-1]
    ang = np.arange(x.shape[0])[:, None] * _yarn_inv_freq(cfg, dim)
    f = cfg.rope_scaling_factor
    m = _yarn_get_mscale(f, cfg.rope_mscale) / _yarn_get_mscale(
        f, cfg.rope_mscale_all_dim)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (dim // 2,)
    cos, sin = (np.cos(ang) * m).reshape(shape), (np.sin(ang) * m).reshape(
        shape)
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    return np.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(a, cfg, h):
    s, nh = h.shape[0], cfg.n_heads
    nope, rope, vd = cfg.nope_head_dim, cfg.rope_head_dim, cfg.v_head_dim
    q = (h @ a["wq"]).reshape(s, nh, nope + rope)
    qn, qr = q[..., :nope], _rope(q[..., nope:], cfg)
    ckv = _rms(h @ a["wdkv"], a["kv_norm"], cfg.norm_eps)
    kr = _rope(h @ a["wkr"], cfg)
    kn = (ckv @ a["wuk"]).reshape(s, nh, nope)
    v = (ckv @ a["wuv"]).reshape(s, nh, vd)
    m = _yarn_get_mscale(cfg.rope_scaling_factor, cfg.rope_mscale_all_dim)
    scale = (nope + rope) ** -0.5 * m * m
    logits = (np.einsum("qhd,khd->hqk", qn, kn)
              + np.einsum("qhd,kd->hqk", qr, kr)) * scale
    logits = np.where(np.tril(np.ones((s, s), bool))[None], logits, -np.inf)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("hqk,khd->qhd", p, v).reshape(s, nh * vd) @ a["wo"]


def _mlp(wg, wu, wd, h):
    return (_silu(h @ wg) * (h @ wu)) @ wd


def _moe(m, cfg, h, experts, held=None):
    """Softmax over the router, greedy top-k (renormalised where the
    config says), the given experts of the stack (router ids ``experts``)
    weighted, plus the shared experts.  ``held``, where given, gains each
    row's count of assignments to ``experts``."""
    logits = h @ m["router"]
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    out = _mlp(m["shared_wg"], m["shared_wu"], m["shared_wd"], h)
    for t in range(h.shape[0]):
        top = np.argsort(-p[t])[:cfg.moe_top_k]
        w = p[t, top]
        if cfg.moe_norm_topk:
            w = w / w.sum()
        for e, we in zip(top, w):
            if e in experts:
                j = experts.index(e)
                out[t] += we * _mlp(m["wg"][j], m["wu"][j], m["wd"][j],
                                    h[t])
                if held is not None:
                    held[t] += 1
    return out


def plain_logits(params, cfg, tokens, held=None):
    """Float64 logits [S, V] of one sequence under causal attention;
    ``held`` [S], where given, gains each row's held assignments summed
    over the MoE layers."""
    w = _np(params)
    x = w["embed"][np.asarray(tokens)]
    experts = list(range(cfg.moe_num_experts))
    for group in ("dense_layers", "moe_layers"):
        stack = w[group]
        for i in range(stack["ln1"]["w"].shape[0]):
            lw = jax.tree.map(lambda a: a[i], stack)
            x = x + _attention(lw["attn"], cfg,
                               _rms(x, lw["ln1"]["w"], cfg.norm_eps))
            h = _rms(x, lw["ln2"]["w"], cfg.norm_eps)
            if group == "dense_layers":
                x = x + _mlp(lw["mlp"]["wg"], lw["mlp"]["wu"],
                             lw["mlp"]["wd"], h)
            else:
                x = x + _moe(lw["moe"], cfg, h, experts, held)
    return _rms(x, w["ln_f"]["w"], cfg.norm_eps) @ w["lm_head"]


# ---------------------------------------------------------------------------
# (a) prefill, then paged decode, against the plain forward
# ---------------------------------------------------------------------------

def _recording_engine(cfg, params, **kw):
    """A paged engine whose prefill and decode logits are kept, per
    request id, in the order the engine computed them."""
    eng = PagedServeEngine(cfg, **kw)
    eng.load(params)
    seen: dict = {}
    queue_order: list = []
    dec = eng._decode_exec

    def decode(p, pools, *a):
        out = dec(p, pools, *a)
        for i, r in enumerate(eng.lanes):
            if r is not None:
                seen.setdefault(r.rid, []).append(np.asarray(out[0][i]))
        return out

    prefill_fn = eng._prefill_fn

    def prefill(*a):
        fn = prefill_fn(*a)

        def run(*args):
            logits, pc = fn(*args)
            rid = queue_order.pop(0)
            seen.setdefault(rid, []).append(np.asarray(logits[0]))
            return logits, pc
        return run

    eng._decode_exec = decode
    eng._prefill_fn = prefill
    return eng, seen, queue_order


def test_prefill_then_paged_decode_match_the_plain_forward(tiny):
    """Bucketed prefill and absorbed paged decode of the tiny model, at
    four lanes with three requests (one lane inactive, prompts padded to
    their buckets), give the plain forward's logits at every position."""
    eng, seen, order = _recording_engine(
        TINY, tiny, max_lanes=4, max_seq=32, block_size=4,
        scheduler=SchedulerConfig(prefill_buckets=(8, 16)))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, TINY.vocab, n).astype(np.int32)
               for n in (5, 11, 3)]
    for p in prompts:
        eng.submit(p, max_new_tokens=6)
    order.extend(r.rid for r in eng.queue)
    done = eng.run_until_drained()
    assert eng.stats["prefill_compiles"] == 2      # buckets 8 and 16
    for req in done:
        seq = np.concatenate([req.prompt, req.output[:-1]])
        want = plain_logits(tiny, TINY, seq)[len(req.prompt) - 1:]
        got = np.stack(seen[req.rid])
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# (b) the held shares add up to the uncut layer
# ---------------------------------------------------------------------------

def _uncut_layer(seed=0):
    cfg = dataclasses.replace(TINY, moe_num_experts=16)
    p = MOE.init_moe(jax.random.PRNGKey(seed), cfg)
    p["router"] = jax.random.normal(jax.random.PRNGKey(seed + 1),
                                    p["router"].shape) / 8.0
    x = jax.random.normal(jax.random.PRNGKey(seed + 2), (2, 9, cfg.d_model))
    return cfg, p, x


def test_held_shares_add_up_to_the_uncut_layer():
    """Four chips of 4 experts each: every chip routes over all 16 and
    computes its own experts' part; those parts, with the shared experts
    counted once, are the uncut layer of the plain forward."""
    cfg, p, x = _uncut_layer()
    flat = x.reshape(-1, cfg.d_model)
    parts = []
    for chip in range(4):
        share = dict(p, **{k: p[k][4 * chip:4 * chip + 4]
                           for k in ("wg", "wu", "wd")})
        y, _, _ = MOE.moe_ffn_tokens(cfg, share, flat,
                                     local_experts=(4 * chip, 4))
        parts.append(np.asarray(y, np.float64))
    shared = _mlp(*(np.asarray(p[k], np.float64)
                    for k in ("shared_wg", "shared_wu", "shared_wd")),
                  np.asarray(flat, np.float64))
    uncut = _moe(_np(p), cfg, np.asarray(flat, np.float64), list(range(16)))
    np.testing.assert_allclose(sum(parts) + shared, uncut, rtol=1e-4,
                               atol=1e-5)
    # the held configuration's layer is chip 0's part plus the shared
    held = dataclasses.replace(cfg, moe_num_experts=4)
    share0 = dict(p, **{k: p[k][:4] for k in ("wg", "wu", "wd")})
    y0, _, _ = MOE.apply_moe(share0, held, x)
    np.testing.assert_allclose(
        np.asarray(y0, np.float64).reshape(-1, cfg.d_model),
        parts[0] + shared, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# (c) dropless, and pad rows / inactive lanes change nothing
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("held", [16, 4])
def test_routing_drops_nothing_when_every_token_picks_one_expert(held):
    """A router that sends every token to expert 0 first (and to 1-5
    after it): under capacity routing at 1.25 most would overflow.  Here
    each assignment is computed, as in the plain forward: by the whole
    layer, and by a chip holding 4 of the 16, whose every token then
    brings 4 held assignments."""
    cfg = dataclasses.replace(TINY, moe_num_experts=held)
    p = MOE.init_moe(jax.random.PRNGKey(3), cfg)
    router = np.zeros((cfg.d_model, 16), np.float32)
    # logits near 4 and 2..1 (x is about 0.9 a dim): every routed
    # expert's weight counts
    router[:, 0] = 4.0 / cfg.d_model
    router[:, 1:6] = np.linspace(2.0, 1.0, 5) / cfg.d_model
    p["router"] = jnp.asarray(router)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(4),
                                  (1, 40, cfg.d_model))) + 0.1
    _, ids, _ = MOE.route(cfg, x.reshape(40, -1) @ p["router"])
    assert np.all(np.asarray(ids)[:, 0] == 0)
    assert np.all(np.sort(np.asarray(ids), -1) == np.arange(6))
    y, _, counted = MOE.apply_moe(p, cfg, x)
    want = _moe(_np(p), cfg, np.asarray(x[0], np.float64),
                list(range(held)))
    np.testing.assert_allclose(np.asarray(y[0]), want, rtol=1e-4, atol=1e-5)
    assert int(counted) == 40 * min(held, cfg.moe_top_k)


def test_pad_rows_and_inactive_lanes_change_no_real_row():
    """Rows marked invalid get no expert: the real rows' outputs are
    those of the real rows alone, whatever the others hold, and the held
    count is of the real rows only."""
    cfg, p, x = _uncut_layer(seed=5)
    held = dataclasses.replace(cfg, moe_num_experts=4)
    p = dict(p, **{k: p[k][:4] for k in ("wg", "wu", "wd")})
    valid = np.ones((2, 9), bool)
    valid[0, 6:] = False          # bucket padding of a prompt
    valid[1] = False              # an inactive lane
    garbage = jnp.where(jnp.asarray(valid)[..., None], x, 1e3)
    y, _, n = MOE.apply_moe(p, held, garbage, valid=jnp.asarray(valid))
    alone, _, n_alone = MOE.apply_moe(p, held, x[:1, :6])
    np.testing.assert_array_equal(np.asarray(y[0, :6]),
                                  np.asarray(alone[0]))
    assert int(n) == int(n_alone)


# ---------------------------------------------------------------------------
# (d) bucketed prefill of an MoE model equals exact-length prefill
# ---------------------------------------------------------------------------

def test_moe_prefill_buckets_and_matches_exact_length(tiny):
    kw = dict(max_lanes=2, max_seq=32, block_size=4)
    outs, logits = {}, {}
    for bucketed in (False, True):
        eng, seen, order = _recording_engine(
            TINY, tiny, scheduler=SchedulerConfig(
                prefill_buckets=(8, 16), bucketed=bucketed), **kw)
        rng = np.random.default_rng(9)
        for n in (5, 11, 7):
            eng.submit(rng.integers(0, TINY.vocab, n).astype(np.int32),
                       max_new_tokens=4)
        order.extend(r.rid for r in eng.queue)
        outs[bucketed] = {r.rid: r.output for r in eng.run_until_drained()}
        logits[bucketed] = seen
        assert eng.stats["prefill_compiles"] == (2 if bucketed else 3)
    assert outs[True] == outs[False]
    for rid, rows in logits[False].items():
        np.testing.assert_allclose(np.stack(logits[True][rid]),
                                   np.stack(rows), rtol=1e-5, atol=1e-5)


def test_decode_counts_held_assignments_of_active_lanes(tiny):
    """The engine's ``moe_held_assignments`` is the decode steps' count
    of (token, held expert) assignments over the MoE layers, of active
    lanes only (two of the three lanes stay empty): the plain forward's
    count at the positions the decode steps fed."""
    eng = PagedServeEngine(TINY, max_lanes=3, max_seq=32, block_size=4)
    eng.load(tiny)
    eng.submit(np.arange(5, dtype=np.int32), max_new_tokens=4)
    (req,) = eng.run_until_drained()
    assert eng.stats["steps"] == 3        # the first token is prefill's
    seq = np.concatenate([req.prompt, req.output[:3]])
    held = np.zeros(len(seq), int)
    plain_logits(tiny, TINY, seq, held)
    assert eng.stats["moe_held_assignments"] == held[5:].sum() > 0


# ---------------------------------------------------------------------------
# (e) YaRN against the formula
# ---------------------------------------------------------------------------

LITE = dataclasses.replace(
    TINY, rope_original_positions=4096, rope_head_dim=64)


def test_yarn_frequencies_and_attention_factor_match_the_formula():
    """DeepSeek-V2-Lite's rope: 64 dims, base 10000, factor 40 over 4096
    positions, beta 32 / 1 -> the ramp runs from dim 10 to dim 23; the
    attention scale gains m**2 with m = 0.1 * 0.707 * ln 40 + 1."""
    got = np.asarray(L.rope_freqs(64, LITE), np.float64)
    want = _yarn_inv_freq(LITE, 64)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    base = 1.0 / 10000.0 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(got[:10], base[:10], rtol=1e-6)
    np.testing.assert_allclose(got[23:], base[23:] / 40, rtol=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert m == pytest.approx(1.2608, abs=1e-4)
    assert L.rope_attn_scale(LITE) == pytest.approx(m * m, rel=1e-12)
    # mscale beside mscale_all_dim scales cos and sin by their ratio
    odd = dataclasses.replace(LITE, rope_mscale=1.0)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 1, 64))
    pos = jnp.arange(5)[None]
    ratio = (0.1 * math.log(40) + 1) / m
    np.testing.assert_allclose(np.asarray(L.apply_rope(x, pos, odd)),
                               ratio * np.asarray(L.apply_rope(x, pos, LITE)),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# (f) the defaults leave today's dense models bit for bit
# ---------------------------------------------------------------------------

#: sha256 of qwen1.5-0.5b SMOKE's prefill, contiguous-decode and paged-decode
#: logits (PRNGKey(0) weights, tokens 37 * i mod vocab), computed on the
#: commit before the held-expert, dropless and YaRN fields existed
QWEN_SMOKE_SHA256 = \
    "01020686387d6f6526074e792421ed5d84af874a6fc6b475bcecc6ededc25aec"


def test_defaults_leave_dense_outputs_bit_identical():
    cfg = get_smoke_config("qwen1.5-0.5b")
    assert not cfg.rope_scaling_factor and cfg.moe_norm_topk
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    toks = jnp.asarray((np.arange(12) * 37 % cfg.vocab)[None], jnp.int32)
    lp, cache = api.prefill(params, {"tokens": toks[:, :11]}, 16)
    ld, _ = api.decode(params, cache, toks[:, 11:12])
    lg, _ = api.paged_decode(
        params, api.paged_init(8, 4, 2), toks[:, :2].reshape(2, 1),
        jnp.asarray([[0, 1, 2, 3], [4, 5, 6, 7]], jnp.int32),
        jnp.asarray([3, 0], jnp.int32), jnp.asarray([True, True]))
    h = hashlib.sha256()
    for a in (lp, ld, lg):
        h.update(np.asarray(a).tobytes())
    assert h.hexdigest() == QWEN_SMOKE_SHA256
    # and the rope is the plain one, to the bit
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 6, 3, 8))
    pos = jnp.broadcast_to(jnp.arange(6), (2, 6))
    inv = 1.0 / (cfg.rope_theta ** (jnp.arange(0, 8, 2, dtype=jnp.float32)
                                    / 8))
    ang = pos[..., None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    old = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    np.testing.assert_array_equal(np.asarray(L.apply_rope(x, pos, cfg)),
                                  np.asarray(old))


# ---------------------------------------------------------------------------
# (g) the in-place decode path: held experts read from the stacks
# ---------------------------------------------------------------------------

#: TINY at widths of whole 128-lane tiles, where the in-place path engages
TILED = dataclasses.replace(TINY, name="dsv2-tiny-tiled", d_model=128,
                            moe_d_ff=128)


@pytest.fixture(scope="module")
def tiled():
    params = build_model(TILED).init(jax.random.PRNGKey(11))
    params["moe_layers"]["moe"]["router"] = jax.random.normal(
        jax.random.PRNGKey(12), params["moe_layers"]["moe"]["router"].shape
    ) / math.sqrt(TILED.d_model)
    return params


def _ragged_only(monkeypatch):
    """Keep every MoE layer on ``ragged_dot`` over the scanned slices."""
    monkeypatch.setattr(MOE, "inplace_stacks", lambda stacked, rows: None)


def _decode_args(cfg, lanes=4):
    api = build_model(cfg)
    pools = api.paged_init(4 * lanes, 4, lanes)
    tokens = jnp.asarray((np.arange(lanes) * 7 + 3)[:, None], jnp.int32)
    tables = jnp.arange(4 * lanes, dtype=jnp.int32).reshape(lanes, 4)
    pos = jnp.asarray([0, 2, 1, 0][:lanes], jnp.int32)
    active = jnp.asarray([True, True, False, True][:lanes])
    return api, (pools, tokens, tables, pos, active)


def test_inplace_decode_matches_ragged_dot_on_slices(tiled, monkeypatch):
    """The paged decode step of the tiled model, its held experts read in
    place, gives the logits and the held count of the same step over
    ``ragged_dot`` on each layer's slice (one lane inactive)."""
    api, args = _decode_args(TILED)
    assert api.paged_moe_inplace(tiled, 4)
    logits, _, held = jax.jit(
        lambda p, *a: api.paged_decode(p, *a))(tiled, *args)
    _ragged_only(monkeypatch)
    assert not api.paged_moe_inplace(tiled, 4)
    want, _, want_held = jax.jit(
        lambda p, *a: api.paged_decode(p, *a))(tiled, *args)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert int(held) == int(want_held) > 0


def test_inplace_kernels_accumulate_over_tiles(monkeypatch):
    """Weight tiles smaller than a layer's widths (d in three tiles: the
    gate/up matmuls' K, the down matmul's N) add up to the plain
    weighted FFN of the layer the index picks."""
    from repro.kernels import held_experts as HE

    monkeypatch.setattr(HE, "BLOCK_BYTES", 128 * 384 * 4)
    n_layers, n, d, ff, t = 3, 4, 384, 256, 10
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    wg = jax.random.normal(ks[0], (n_layers, n, d, ff)) / 20
    wu = jax.random.normal(ks[1], (n_layers, n, d, ff)) / 20
    wd = jax.random.normal(ks[2], (n_layers, n, ff, d)) / 16
    x = jax.random.normal(ks[3], (t, d))
    w = np.array(jax.random.uniform(ks[4], (t, n)))
    w[np.arange(t) % 3 == 0] = 0.0          # rows that picked no expert
    y = HE.held_experts_ffn(x, wg, wu, wd, jnp.int32(1), jnp.asarray(w))
    g = _np({"wg": wg[1], "wu": wu[1], "wd": wd[1]})
    want = sum(w[:, e:e + 1] * _mlp(g["wg"][e], g["wu"][e], g["wd"][e],
                                    np.asarray(x, np.float64))
               for e in range(n))
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(y)[w.sum(1) == 0], 0.0)


def _tiled_layer(seed):
    cfg = dataclasses.replace(TILED, moe_num_experts=16)
    p = MOE.init_moe(jax.random.PRNGKey(seed), cfg)
    p["router"] = jax.random.normal(jax.random.PRNGKey(seed + 1),
                                    p["router"].shape) / 8.0
    held = dataclasses.replace(cfg, moe_num_experts=4)
    stacks = {k: p[k][None, :4] for k in ("wg", "wu", "wd")}
    rest = {k: v for k, v in p.items() if k not in stacks}
    return cfg, held, p, stacks, rest


def test_inplace_inactive_lanes_and_unpicked_tokens_change_no_real_row():
    """Rows marked invalid, whatever they hold, change no real row and
    add no held assignment; a real row that picked no held expert gets
    an exact zero from the held experts."""
    cfg, held, p, stacks, rest = _tiled_layer(seed=21)
    x = jax.random.normal(jax.random.PRNGKey(23), (1, 12, cfg.d_model))
    valid = np.ones((1, 12), bool)
    valid[0, 8:] = False
    garbage = jnp.where(jnp.asarray(valid)[..., None], x, 1e3)
    at = (stacks, jnp.int32(0))
    y, _, n = MOE.apply_moe(rest, held, garbage, valid=jnp.asarray(valid),
                            held_at=at)
    alone, _, n_alone = MOE.apply_moe(rest, held, x[:, :8], held_at=at)
    np.testing.assert_allclose(np.asarray(y[0, :8]), np.asarray(alone[0]),
                               rtol=1e-6, atol=1e-6)
    assert int(n) == int(n_alone)
    # the held experts alone (no shared experts): rows whose top-k misses
    # experts 0-3 read exactly 0
    bare = dataclasses.replace(held, moe_shared_experts=0)
    flat = x[0]
    _, ids, _ = MOE.route(bare, flat @ p["router"])
    none = ~np.any(np.asarray(ids) < 4, axis=1)
    assert none.any() and not none.all()
    y, _, n = MOE.moe_ffn_tokens(bare, rest, flat, held_at=at)
    np.testing.assert_array_equal(np.asarray(y)[none], 0.0)
    ragged = dict(rest, **{k: v[0] for k, v in stacks.items()})
    want, _, n_want = MOE.moe_ffn_tokens(bare, ragged, flat)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-5,
                               atol=1e-6)
    assert int(n) == int(n_want)


def test_inplace_computes_all_tokens_on_one_held_expert():
    """Every token picks held expert 0 first (T rows in one group) and
    experts 1-5 after it: the in-place path computes each assignment, as
    the plain forward does."""
    cfg = dataclasses.replace(TILED, moe_num_experts=4)
    p = MOE.init_moe(jax.random.PRNGKey(31), cfg)
    router = np.zeros((cfg.d_model, 16), np.float32)
    router[:, 0] = 4.0 / cfg.d_model
    router[:, 1:6] = np.linspace(2.0, 1.0, 5) / cfg.d_model
    p["router"] = jnp.asarray(router)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(32),
                                  (1, 40, cfg.d_model))) + 0.1
    _, ids, _ = MOE.route(cfg, x.reshape(40, -1) @ p["router"])
    assert np.all(np.asarray(ids)[:, 0] == 0)
    stacks = {k: p[k][None] for k in ("wg", "wu", "wd")}
    rest = {k: v for k, v in p.items() if k not in stacks}
    y, _, counted = MOE.apply_moe(rest, cfg, x,
                                  held_at=(stacks, jnp.int32(0)))
    want = _moe(_np(p), cfg, np.asarray(x[0], np.float64), list(range(4)))
    np.testing.assert_allclose(np.asarray(y[0]), want, rtol=1e-4, atol=1e-5)
    assert int(counted) == 40 * 4


@pytest.mark.parametrize("cfg_name", ["tiled", "tiny", "qwen"])
def test_moe_inplace_steps_counts_decode_steps(cfg_name, tiled, tiny):
    """``moe_inplace_steps`` counts every decode step where the held
    experts are read in place, and stays 0 where the path does not
    engage: widths that are not whole 128-lane tiles, a model with no
    MoE layer."""
    if cfg_name == "qwen":
        cfg = get_smoke_config("qwen1.5-0.5b")
        params = build_model(cfg).init(jax.random.PRNGKey(0))
    else:
        cfg, params = {"tiled": (TILED, tiled), "tiny": (TINY, tiny)}[
            cfg_name]
    eng = PagedServeEngine(cfg, max_lanes=3, max_seq=32, block_size=4)
    eng.load(params)
    for n in (5, 9):
        eng.submit(np.arange(n, dtype=np.int32) % cfg.vocab,
                   max_new_tokens=4)
    eng.run_until_drained()
    assert eng.stats["steps"] > 0
    want = eng.stats["steps"] if cfg_name == "tiled" else 0
    assert eng.stats["moe_inplace_steps"] == want


def _layer_slices(text, stack_shape):
    """The dynamic-slice ops of ``text`` (StableHLO) that cut one whole
    layer out of a ``stack_shape`` expert stack."""
    big = "tensor<" + "x".join(map(str, stack_shape)) + "xf32>"
    one = "tensor<" + "x".join(map(str, (1,) + stack_shape[1:])) + "xf32>"
    return [line for line in text.splitlines()
            if "dynamic_slice" in line and big in line and one in line]


def test_lowered_decode_has_no_slice_of_the_expert_stacks(tiled,
                                                         monkeypatch):
    """The decode program reads the expert stacks whole: no dynamic-slice
    cuts a layer out of a [L_moe, n, K, N] stack, as the scan over the
    stacks does on the ragged path."""
    api, args = _decode_args(TILED)
    shape = tuple(tiled["moe_layers"]["moe"]["wg"].shape)
    assert shape == (3, 4, 128, 128)

    def lowered():
        return jax.jit(lambda p, *a: api.paged_decode(p, *a)).lower(
            tiled, *args).as_text()

    assert _layer_slices(lowered(), shape) == []
    _ragged_only(monkeypatch)
    assert len(_layer_slices(lowered(), shape)) >= 3
