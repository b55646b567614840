"""The serving surface: request model, validation, submission and
streaming, beside the engine's cache and decode (``serve.engine``):

  * ``Request`` / ``validate_request``: the request dataclass and the
    horizon check applied at submit time, with typed rejection errors.
  * ``EngineBase``: ``submit`` / ``submit_text`` for token prompts,
    ``submit_audio_stream`` for raw audio, ``run_until_drained``, the
    planning-override context, and the whole chunked-streaming
    machinery (planned audio frontend, incremental encoder state,
    per-step chunk feeds).  ``serve.engine.PagedServeEngine`` adds how
    a prefill cache lands in the block pools and how decode executes.
  * ``make_engine(cfg, **kw)``: the one constructor callers use
    (``launch.serve``, benches, tests).

Streaming admission contract (``kind == "audio"`` requests, encdec
only): the utterance arrives as fixed-size sample chunks
(``AudioFrontend.split``).  Admission feeds chunk 0 through the planned
frontend -> incremental encoder -> per-layer cross K/V, then runs the
*decoder-only* prompt pass (``api.stream_prefill``) against the
partially-filled encoder cache — decode starts before utterance end.
Each subsequent ``step()`` feeds one more chunk per streaming lane
through the same jitted functions and appends its K/V in place
(``dynamic_update_slice`` at the lane's fill clock); chunked
cross-attention masks rows past ``enc_len``, so positions the decoder
never saw stay exactly invisible.  The decode executable takes no new
inputs and is never retraced — ``decode_compiles`` stays 1 while
streaming.  A preempted audio request replays its consumed chunks
bit-identically on re-admission (same jitted per-chunk executables).
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import build_model
from repro.models.model import cache_dtype_of
from repro.kernels import planned

from .frontend import AudioFrontend, FrontendConfig


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray           # [S] int32
    max_new_tokens: int
    extra: dict | None = None    # frames / patch embeds for audio/vlm
    output: list = dataclasses.field(default_factory=list)
    done: bool = False
    # streaming audio: kind == "audio" requests carry their utterance as
    # chunk-sized sample blocks; ``fed`` counts chunks already encoded
    # (preserved across preemption so re-admission replays exactly them)
    kind: str = "text"
    chunks: list | None = None
    fed: int = 0
    # host-clock stamps (time.perf_counter): queued by submit, first
    # prefill dispatched; and the prompt rows prefilled for it, real and
    # bucket-padded, summed over re-admissions after preemption
    t_submit: float = 0.0
    t_admit: float | None = None
    prefill_tokens: int = 0
    prefill_padded_tokens: int = 0


#: Prefix of the engines' profiler spans (``jax.profiler.TraceAnnotation``
#: is near free while no profile is being taken).
SPAN_PREFIX = "repro/serve."


def span(name: str, **args):
    """A profiler span ``repro/serve.<name>`` carrying ``args``."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name, **args)


def validate_request(prompt, max_new_tokens: int, max_seq: int,
                     extra_rows: int = 0) -> None:
    """Reject requests that would run past the sequence horizon.

    ``decode_step`` advances ``pos`` unconditionally and the cache write
    (``dynamic_update_slice``) clamps at ``max_seq`` — an overlong
    request would silently overwrite the last cache row in place
    instead of failing.  Refuse it at submit time."""
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got "
                         f"{max_new_tokens}")
    total = extra_rows + len(prompt) + max_new_tokens
    if total > max_seq:
        raise ValueError(
            f"request needs {total} cache rows (prompt {len(prompt)}"
            f"{f' + {extra_rows} extra' if extra_rows else ''} + "
            f"max_new_tokens {max_new_tokens}) > max_seq {max_seq}: "
            "the decode write would silently clamp at the horizon, "
            "overwriting the last cache row; raise max_seq or shorten "
            "the request")


@dataclasses.dataclass
class _StreamState:
    """Per-lane streaming state: the request it belongs to (identity-
    checked so a recycled lane drops stale state), the incremental
    encoder cache, and the frontend's FIR carry."""
    req: Request
    ec: dict
    carry: jax.Array


class EngineBase:
    """Request/submission/streaming layer of the serving engine.

    The engine provides device-state specifics via three hooks:
    ``_lane_request(lane)`` (who holds the lane), ``_append_enc(lane,
    ek, ev, start, new_len)`` (write one chunk's cross K/V into the
    lane's encoder buffers), and their own admit/step/decode paths.
    """

    def __init__(self, cfg, *, max_seq: int, policy=None, target=None,
                 frontend: AudioFrontend | None = None):
        self.cfg = cfg
        self.policy = policy
        # optional execution target for the serving GEMMs — pass a
        # core.HierarchicalTarget to split them column/row-parallel over
        # the outer tp axis (None inherits the ambient planned config)
        self.target = target
        self.api = build_model(cfg)
        self.max_seq = max_seq
        self.params = None
        self.queue: list[Request] = []
        self.finished: list[Request] = []
        self._next_rid = 0
        self.plan_report: dict = {}
        self.autotune_report: dict = {}
        # audio streaming is an encdec capability: default frontend
        # geometry targets the config's embedding width
        if frontend is None and cfg.family == "encdec":
            frontend = AudioFrontend(FrontendConfig(d_model=cfg.d_model))
        self.frontend = frontend if cfg.family == "encdec" else None
        self._streams: dict[int, _StreamState] = {}
        self._stream_jits: dict | None = None

    # -- planning context ---------------------------------------------------
    def _plan_ctx(self):
        """The planning override every trace runs under: the engine's
        policy, plus its execution target when one was given (kept
        ambient otherwise — an explicit None would clobber a process-
        level ``planned.configure(target=...)``)."""
        if self.target is not None:
            return planned.override(policy=self.policy, target=self.target)
        return planned.override(policy=self.policy)

    # -- submission ---------------------------------------------------------
    def _extra_rows(self, extra: dict | None) -> int:
        if extra and self.cfg.family == "vlm" and "extra_embeds" in extra:
            return self.cfg.vlm_patches
        return 0

    def submit(self, prompt: np.ndarray, max_new_tokens: int = 16,
               extra: dict | None = None) -> int:
        prompt = np.asarray(prompt, np.int32)
        validate_request(prompt, max_new_tokens, self.max_seq,
                         self._extra_rows(extra))
        rid = self._next_rid
        self._next_rid += 1
        self.queue.append(Request(rid, prompt, max_new_tokens, extra,
                                  t_submit=time.perf_counter()))
        return rid

    # explicit-name alias so call sites read symmetrically with
    # submit_audio_stream
    submit_text = submit

    def submit_audio_stream(self, samples, max_new_tokens: int = 16,
                            prompt: np.ndarray | None = None) -> int:
        """Queue a chunked audio request: ``samples`` is a whole number
        of frontend chunks (``frontend.cfg.chunk_samples`` each); the
        decoder prompt defaults to a single BOS-like token 0."""
        if self.frontend is None:
            raise ValueError(
                f"audio streaming needs an encdec model with an audio "
                f"frontend; family {self.cfg.family!r} has none")
        chunks = self.frontend.split(samples)
        n_frames = len(chunks) * self.frontend.cfg.frames_per_chunk
        if n_frames > self.cfg.enc_frames:
            raise ValueError(
                f"audio stream is {n_frames} encoder frames "
                f"({len(chunks)} chunks x "
                f"{self.frontend.cfg.frames_per_chunk}) > enc_frames "
                f"{self.cfg.enc_frames}: the encoder cache cannot hold "
                "the utterance; split it across requests")
        prompt = np.asarray([0] if prompt is None else prompt, np.int32)
        validate_request(prompt, max_new_tokens, self.max_seq)
        rid = self._next_rid
        self._next_rid += 1
        self.queue.append(Request(rid, prompt, max_new_tokens,
                                  kind="audio", chunks=chunks,
                                  t_submit=time.perf_counter()))
        return rid

    def step(self) -> int:  # provided by the engine subclass
        raise NotImplementedError

    def run_until_drained(self, max_steps: int = 1000) -> list[Request]:
        for _ in range(max_steps):
            if self.step() == 0 and not self.queue:
                break
        return self.finished

    # -- streaming machinery ------------------------------------------------
    def _lane_request(self, lane: int) -> Request | None:
        raise NotImplementedError

    def _append_enc(self, lane: int, ek, ev, start: int,
                    new_len: int) -> None:
        raise NotImplementedError

    def _stream_fns(self) -> dict:
        """Jitted per-chunk streaming functions, built once per engine.
        Every call sees the same shapes ([C]-frame chunks, [f_max]
        buffers, traced lane/start scalars), so each compiles exactly
        once — streaming steady state runs zero new traces."""
        if self._stream_jits is None:
            api = self.api

            def buf_write(buf, upd, start):
                # admission-side [nl, 1, f_max, hkv, hd] accumulation
                return jax.lax.dynamic_update_slice(
                    buf, upd, (0, 0, start, 0, 0))

            def lane_append(ck, cv, cl, ek, ev, lane, start, new_len):
                # in-place chunk append into the engine's lane buffers
                # ([nl, lanes, f_max, hkv, hd]) + fill-clock bump
                return (jax.lax.dynamic_update_slice(
                            ck, ek, (0, lane, start, 0, 0)),
                        jax.lax.dynamic_update_slice(
                            cv, ev, (0, lane, start, 0, 0)),
                        cl.at[lane].set(new_len))

            self._stream_jits = {
                "enc_step": jax.jit(
                    lambda p, ec, fc: api.enc_step(p, ec, fc)),
                "enc_kv": jax.jit(lambda p, e: api.enc_kv(p, e)),
                "buf_write": jax.jit(buf_write),
                "lane_append": jax.jit(lane_append),
            }
        return self._stream_jits

    def _zero_enc_kv(self):
        cfg = self.cfg
        shape = (cfg.n_layers, 1, cfg.enc_frames, cfg.n_kv_heads, cfg.hd)
        z = jnp.zeros(shape, cache_dtype_of(cfg))
        return z, z

    def _encode_chunk(self, state_carry, state_ec, chunk):
        """One chunk through frontend -> encoder -> cross K/V; returns
        (carry', ec', ek, ev) — the single code path admission replay
        and per-step feeding both run."""
        fns = self._stream_fns()
        carry, feats = self.frontend.chunk_features(state_carry, chunk)
        ec, enc_out = fns["enc_step"](self.params, state_ec, feats[None])
        ek, ev = fns["enc_kv"](self.params, enc_out)
        return carry, ec, ek, ev

    def _stream_admit_state(self, req: Request):
        """Replay the chunks consumed so far (at least one: initial
        admission feeds chunk 0) into fresh admission-side buffers.
        Returns (enc_k [nl,1,f_max,..], enc_v, enc_len [1], ec, carry).
        A preempted request re-runs the identical jitted executables
        over the identical chunks, so the rebuilt encoder state is
        bitwise the state it lost."""
        fns = self._stream_fns()
        C = self.frontend.cfg.frames_per_chunk
        carry = self.frontend.init_state()
        ec = self.api.enc_init(1, self.cfg.enc_frames)
        ck, cv = self._zero_enc_kv()
        n = max(req.fed, 1)
        for i in range(n):
            carry, ec, ek, ev = self._encode_chunk(carry, ec,
                                                   req.chunks[i])
            ck = fns["buf_write"](ck, ek, i * C)
            cv = fns["buf_write"](cv, ev, i * C)
        req.fed = n
        enc_len = jnp.full((1,), n * C, jnp.int32)
        return ck, cv, enc_len, ec, carry

    def _feed_streams(self) -> None:
        """Advance every streaming lane by one chunk (called once per
        ``step()``, inside the plan context).  Lanes whose request
        finished or was preempted drop their state; fully-fed lanes
        just keep decoding against the complete encoder cache."""
        if not self._streams:
            return
        C = self.frontend.cfg.frames_per_chunk
        for lane in list(self._streams):
            st = self._streams[lane]
            if self._lane_request(lane) is not st.req:
                del self._streams[lane]
                continue
            req = st.req
            if req.fed >= len(req.chunks):
                continue
            i = req.fed
            st.carry, st.ec, ek, ev = self._encode_chunk(
                st.carry, st.ec, req.chunks[i])
            self._append_enc(lane, ek, ev, i * C, (i + 1) * C)
            req.fed = i + 1


def make_engine(cfg, kind: str = "paged", **kwargs):
    """The one serving-engine constructor: the block-paged
    continuous-batching ``PagedServeEngine``.  All keyword arguments
    pass through to it.  ``kind`` names that engine, ``"paged"``, and
    any other value is refused."""
    from .engine import PagedServeEngine
    if kind != "paged":
        raise ValueError(
            f"unknown engine kind {kind!r}: expected 'paged'")
    return PagedServeEngine(cfg, **kwargs)
