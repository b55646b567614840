"""Streaming multimodal serving: the planned audio frontend, chunked
encoder parity, and chunked admission through the unified engine
surface (serve/api.py).

The contracts pinned here:

  * chunked frontend features are *bitwise* identical to offline
    whole-utterance features, for int16 and float32;
  * the chunked encoder (incremental ``encode_chunk``) is bitwise
    identical to offline whole-utterance prefill through the same
    per-chunk computation (``prefill_streaming``), and equal within
    float rounding to the one-shot block-causal ``encode(chunk=C)`` and
    to the engines' per-step feed (different XLA programs, see
    ``_assert_close``);
  * an audio stream served by the engine produces the tokens of the
    same chunk schedule decoded alone through the model's contiguous
    decode, and lane encoder state equal to the offline comparator;
  * streaming steady state never replans, never measures, and never
    touches the AOT decode executable (``decode_compiles == 1``);
  * the engine has one validation surface with typed rejections (no
    duplicated ``Request``/``validate_request``).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.core import autotune
from repro.core.mapper import plan_cache_info
from repro.models import build_model
from repro.models import encdec as E
from repro.models.model import cache_dtype_of
from repro.serve import (AudioFrontend, FrontendConfig, make_engine,
                         synth_samples)


CFG = get_smoke_config("whisper-base")
_PARAMS = None


def _params():
    global _PARAMS
    if _PARAMS is None:
        _PARAMS = build_model(CFG).init(jax.random.PRNGKey(42))
    return _PARAMS


def _engine(**kw):
    kw.setdefault("max_lanes", 2)
    kw.setdefault("block_size", 8)
    eng = make_engine(CFG, max_seq=64, **kw)
    eng.load(_params())
    return eng


def _assert_close(got, want, *, rtol, atol):
    """Elementwise ``|got - want| <= atol + rtol * |want|`` in float32.

    Used where the two sides are the same math compiled as different
    XLA programs (other shapes, or one program per chunk against one
    for the utterance): XLA fuses and orders their reductions
    differently, so floats agree to rounding, not bitwise."""
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=rtol, atol=atol)


def _frames(seed=7):
    rng = np.random.default_rng(seed)
    return rng.standard_normal(
        (CFG.enc_frames, CFG.d_model)).astype(np.float32)


# ---------------------------------------------------------------------------
# frontend: chunked == offline, planned stages resolve
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", ["int16", "float32"])
def test_frontend_chunked_bitwise_equals_offline(dtype):
    fc = FrontendConfig(d_model=CFG.d_model, dtype=dtype)
    fe = AudioFrontend(fc)
    samples = synth_samples(fc, 4, seed=5)
    offline = fe.offline_features(samples)
    carry = fe.init_state()
    chunks = []
    for chunk in fe.split(samples):
        carry, f = fe.chunk_features(carry, chunk)
        chunks.append(f)
    streamed = jnp.concatenate(chunks, axis=0)
    assert offline.shape == (4 * fc.frames_per_chunk, CFG.d_model)
    assert (np.asarray(offline) == np.asarray(streamed)).all()


@pytest.mark.parametrize("dtype", ["int16", "float32"])
def test_frontend_stages_are_planned(dtype):
    from repro.kernels import planned

    fc = FrontendConfig(d_model=CFG.d_model, dtype=dtype)
    fe = AudioFrontend(fc)
    before = planned.planned_report()
    fe.offline_features(synth_samples(fc, 2, seed=1))
    delta = planned.report_delta(before, planned.planned_report())
    for site in ("frontend.fir", "frontend.fft2d", "frontend.conv2d"):
        assert site in delta, (site, sorted(delta))
        assert delta[site]["planned"] > 0, (site, delta[site])
        assert delta[site]["fallback"] == 0, (site, delta[site])


def test_frontend_rejects_ragged_streams():
    fe = AudioFrontend(FrontendConfig(d_model=CFG.d_model))
    with pytest.raises(ValueError, match="multiple of"):
        fe.split(np.zeros(fe.cfg.chunk_samples + 1, np.int16))
    with pytest.raises(ValueError, match="multiple of"):
        fe.split(np.zeros(0, np.int16))


# ---------------------------------------------------------------------------
# chunked encoder parity (model level, no engine)
# ---------------------------------------------------------------------------

def test_incremental_encoder_bitwise_equals_offline_prefill():
    """encode_chunk fed chunk by chunk == prefill_streaming over the
    whole utterance: identical enc caches and identical first logits."""
    params = _params()
    fc = FrontendConfig(d_model=CFG.d_model)
    fe = AudioFrontend(fc)
    feats = fe.offline_features(synth_samples(fc, 4, seed=9))[None]
    C = fc.frames_per_chunk

    ec = E.init_enc_cache(CFG, 1)
    ck = cv = None
    for i in range(feats.shape[1] // C):
        ec, out = E.encode_chunk(params, CFG, ec, feats[:, i*C:(i+1)*C])
        ek, ev = E.enc_kv_chunk(params, CFG, out, cache_dtype_of(CFG))
        ck = ek if ck is None else jnp.concatenate([ck, ek], 2)
        cv = ev if cv is None else jnp.concatenate([cv, ev], 2)

    logits, cache, ec_off = E.prefill_streaming(
        params, CFG, feats, jnp.asarray([[0]]), 64, C,
        cache_dtype=cache_dtype_of(CFG))
    F = feats.shape[1]
    assert (np.asarray(cache["enc_k"][:, :, :F]) == np.asarray(ck)).all()
    assert (np.asarray(cache["enc_v"][:, :, :F]) == np.asarray(cv)).all()
    for leaf in ("k", "v", "len"):
        assert (np.asarray(ec[leaf]) == np.asarray(ec_off[leaf])).all()


def test_block_causal_encode_equals_incremental():
    """The one-shot block-causal mask (encode(chunk=C)) computes what
    incremental chunk feeding does."""
    params = _params()
    rng = np.random.default_rng(3)
    C = 8
    frames = jnp.asarray(
        rng.standard_normal((1, CFG.enc_frames, CFG.d_model)), jnp.float32)
    one_shot = E.encode(params, CFG, frames, chunk=C)
    ec = E.init_enc_cache(CFG, 1)
    outs = []
    for i in range(CFG.enc_frames // C):
        ec, o = E.encode_chunk(params, CFG, ec, frames[:, i*C:(i+1)*C])
        outs.append(o)
    inc = jnp.concatenate(outs, 1)
    # float32 encoder: one shot softmaxes over F keys, each chunk over
    # f_max keys with the rest masked — reductions of other lengths,
    # a few float32 ulps apart per layer
    _assert_close(one_shot, inc, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# engine streaming end to end
# ---------------------------------------------------------------------------

def _streamed_reference(api, params, fe, samples, max_new, max_seq=64):
    """The greedy tokens of one audio stream decoded alone through the
    model's contiguous decode, on the engine's schedule: the prompt
    (token 0) prefilled against the first chunk's encoder K/V, then one
    more chunk appended before each decode step until all are fed."""
    chunks = fe.split(samples)
    C = fe.cfg.frames_per_chunk
    enc_step, enc_kv = jax.jit(api.enc_step), jax.jit(api.enc_kv)
    carry, ec = fe.init_state(), api.enc_init(1, CFG.enc_frames)
    shape = (CFG.n_layers, 1, CFG.enc_frames, CFG.n_kv_heads, CFG.hd)
    enc = {k: jnp.zeros(shape, cache_dtype_of(CFG))
           for k in ("enc_k", "enc_v")}

    def feed(i):
        nonlocal carry, ec
        carry, feats = fe.chunk_features(carry, chunks[i])
        ec, out = enc_step(params, ec, feats[None])
        for k, kv in zip(("enc_k", "enc_v"), enc_kv(params, out)):
            enc[k] = enc[k].at[:, :, i * C:(i + 1) * C].set(kv)
        enc["enc_len"] = jnp.full((1,), (i + 1) * C, jnp.int32)

    feed(0)
    logits, cache = jax.jit(api.stream_prefill, static_argnums=5)(
        params, enc["enc_k"], enc["enc_v"], enc["enc_len"],
        jnp.asarray([[0]], jnp.int32), max_seq)
    decode = jax.jit(api.decode)
    out, fed = [int(jnp.argmax(logits[0]))], 1
    while len(out) < max_new:
        if fed < len(chunks):
            feed(fed)
            fed += 1
        logits, cache = decode(params, dict(cache, **enc),
                               jnp.asarray([[out[-1]]], jnp.int32))
        out.append(int(jnp.argmax(logits[0])))
    return out


def test_streamed_audio_slot_equals_paged_and_offline():
    """One utterance through the engine: the token stream of the same
    chunk schedule decoded alone through the contiguous decode, lane
    encoder state equal to the offline comparator, and decode starting
    before the stream completes."""
    params = _params()
    paged = _engine()
    fe = paged.frontend
    samples = synth_samples(fe.cfg, 4, seed=3)

    rid = paged.submit_audio_stream(samples, max_new_tokens=8)
    req = {r.rid: r for r in paged.run_until_drained()}[rid]
    assert req.done and len(req.output) == 8
    assert req.fed == 4, "all chunks must be consumed"
    assert list(req.output) == _streamed_reference(
        paged.api, params, fe, samples, 8)

    # lane 0's encoder K/V (device state survives release) must equal
    # the offline whole-utterance comparator.  The engine runs each
    # chunk as its own jitted encoder and K/V programs, the comparator
    # dispatches the utterance op by op, so a bfloat16 cache value can
    # round one or two ulps (2**-7 of the element each) apart; after
    # the cancellation of a projection that shows up on small elements,
    # hence 2**-12 absolute, 1/32 of a bf16 ulp at the values' scale (~4)
    feats = fe.offline_features(samples)
    _, cache, _ = E.prefill_streaming(
        params, CFG, feats[None], jnp.asarray([[0]]), 64,
        fe.cfg.frames_per_chunk, cache_dtype=cache_dtype_of(CFG))
    _assert_close(paged.kv.pools["enc_k"][:, 0], cache["enc_k"][:, 0],
                  rtol=2**-6, atol=2**-12)
    _assert_close(paged.kv.pools["enc_v"][:, 0], cache["enc_v"][:, 0],
                  rtol=2**-6, atol=2**-12)

    # chunked admission means decode ran while chunks were still
    # arriving: 8 tokens over 4 chunks needs fewer steps than a
    # sequential (encode-all, then decode) schedule would
    assert paged.stats["steps"] >= 1
    assert paged.stats["decode_compiles"] == 1


def test_streaming_decode_starts_before_utterance_end():
    """After one step, the audio lane has emitted tokens but not yet
    consumed its chunks — decode genuinely overlaps the stream."""
    eng = _engine()
    fc = eng.frontend.cfg
    rid = eng.submit_audio_stream(synth_samples(fc, 4, seed=1),
                                  max_new_tokens=8)
    eng.step()
    req = eng.lanes[0]
    assert req is not None and req.rid == rid
    assert len(req.output) >= 2      # prefill token + 1 decode token
    assert req.fed < 4               # stream still arriving
    eng.run_until_drained()
    assert eng.stats["decode_compiles"] == 1


def test_mixed_text_audio_under_preemption():
    """Text + audio sharing an oversubscribed block pool: preemption
    fires, prefers text victims, and every request still finishes with
    its full budget."""
    eng = _engine(max_lanes=3, block_size=4, num_blocks=10)
    fc = eng.frontend.cfg
    samples = synth_samples(fc, 3, seed=2)
    frames = _frames()
    rid_a = eng.submit_audio_stream(samples, max_new_tokens=10)
    rids_t = [eng.submit_text(np.arange(4) + 1 + i, max_new_tokens=10,
                              extra={"frames": frames})
              for i in range(2)]
    done = {r.rid: r for r in eng.run_until_drained(max_steps=200)}
    assert eng.stats["preemptions"] > 0, "pool pressure must preempt"
    for rid in (rid_a, *rids_t):
        assert done[rid].done and len(done[rid].output) == 10
    assert done[rid_a].fed == 3

    # the audio stream's tokens must match an unpressured run — the
    # replayed chunks reproduce the lost encoder state bit-identically
    calm = _engine(max_lanes=3)
    rid_c = calm.submit_audio_stream(samples, max_new_tokens=10)
    calm_done = {r.rid: r for r in calm.run_until_drained()}
    assert calm.stats["preemptions"] == 0
    assert list(done[rid_a].output) == list(calm_done[rid_c].output)


def test_streaming_steady_state_no_replanning_no_measurement():
    """Second identical stream on a warm engine: zero plan-cache
    misses, zero autotune traffic, decode executable untouched."""
    eng = _engine()
    fc = eng.frontend.cfg
    samples = synth_samples(fc, 4, seed=4)
    eng.submit_audio_stream(samples, max_new_tokens=6)
    eng.run_until_drained()
    misses = plan_cache_info().misses
    tune0 = autotune.counters()
    compiles0 = dict(eng.stats)
    eng.submit_audio_stream(samples, max_new_tokens=6)
    eng.run_until_drained()
    assert plan_cache_info().misses == misses
    tune1 = autotune.counters()
    assert tune1["measure_calls"] == tune0["measure_calls"]
    assert tune1["misses"] == tune0["misses"]
    assert eng.stats["decode_compiles"] == compiles0["decode_compiles"] == 1
    assert eng.stats["prefill_compiles"] == compiles0["prefill_compiles"]


# ---------------------------------------------------------------------------
# one shared request surface (serve/api.py)
# ---------------------------------------------------------------------------

def test_engine_module_has_no_duplicate_request_surface():
    """The request model and validation live once, in serve.api."""
    import repro.serve.api as api
    import repro.serve.engine as engine

    assert engine.Request is api.Request
    assert engine.validate_request is api.validate_request
    assert not hasattr(engine, "_validate_request")
    assert engine.PagedServeEngine.submit is api.EngineBase.submit
    assert (engine.PagedServeEngine.run_until_drained
            is api.EngineBase.run_until_drained)


@pytest.mark.parametrize("kind", ["paged"])
def test_validation_rejections_identical_across_engines(kind):
    eng = _engine(kind=kind)
    with pytest.raises(ValueError,
                       match=r"max_new_tokens must be >= 1, got 0"):
        eng.submit(np.arange(3), max_new_tokens=0)
    with pytest.raises(ValueError, match=r"> max_seq 64"):
        eng.submit(np.arange(60), max_new_tokens=10)
    # audio-specific rejections route through the same surface
    fc = eng.frontend.cfg
    with pytest.raises(ValueError, match="multiple of"):
        eng.submit_audio_stream(np.zeros(7, np.int16))
    too_long = synth_samples(fc, CFG.enc_frames
                             // fc.frames_per_chunk + 1, seed=0)
    with pytest.raises(ValueError, match="enc_frames"):
        eng.submit_audio_stream(too_long)
    with pytest.raises(ValueError,
                       match=r"max_new_tokens must be >= 1, got -1"):
        eng.submit_audio_stream(synth_samples(fc, 1, seed=0),
                                max_new_tokens=-1)


def test_audio_submit_rejected_for_non_encdec():
    cfg = get_smoke_config("qwen1.5-0.5b")
    eng = make_engine(cfg, max_lanes=1, max_seq=32)
    with pytest.raises(ValueError, match="audio"):
        eng.submit_audio_stream(np.zeros(804, np.int16))


@pytest.mark.parametrize("kind", ["ring", "slot"])
def test_make_engine_rejects_unknown_kind(kind):
    with pytest.raises(ValueError, match="unknown engine kind"):
        make_engine(CFG, kind=kind)
