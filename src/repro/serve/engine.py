"""The serving engine: continuous batching over a block-paged KV cache
(``paged_cache``).

``PagedServeEngine`` is an ``api.EngineBase`` subclass — the request
model, validation, submission (``submit`` / ``submit_text`` /
``submit_audio_stream``), drain loop, planning context, and the whole
chunked audio-streaming machinery live in ``serve.api``.  What remains
here is how a prefill cache lands in the block pools and how decode
executes.  Construct it through ``serve.make_engine(cfg, **kw)``.

  * K/V lives in fixed-size blocks on the sequence axis; each request
    holds a host-side block table.  Admit/evict/grow is a host table
    edit — the AOT-compiled decode executable takes static-shape
    (tokens, block_tables, pos, active) inputs and is compiled exactly
    once in ``load()``; joining or finishing a request can never
    recompile it (``jax.jit(...).lower(...).compile()`` executables
    *error* on shape mismatch rather than retrace).
  * Prefills are bucketed (``scheduler``): prompts pad to the next
    bucket length so the jitted prefill compiles once per bucket, and
    the scheduler packs at most a few prefills into steps where decode
    lanes sit idle instead of stalling all in-flight decodes behind a
    burst.
  * When the block pool runs dry mid-flight, the youngest active
    request is preempted: its blocks free instantly, it re-queues with
    its generated tokens folded into the prompt, and recomputes on
    re-admission (output-transparent — same context, same greedy
    tokens).  Text lanes are preferred victims over streaming audio
    lanes (an audio victim must also replay its consumed chunks).

Streaming audio requests (encdec) admit after their *first* chunk:
the planned frontend + incremental encoder produce a partial encoder
cache, the decoder prompt prefills against it (``stream_prefill``),
and each engine ``step()`` feeds one more chunk per streaming lane in
place — decode output starts before the utterance ends, and the decode
executable itself never changes shape (``decode_compiles`` stays 1).

Every serving GEMM routes through ``kernels.planned``; ``load()``
traces and compiles up front (``plan_report`` is the warmup's delta).

Greedy sampling (argmax); temperature hooks included but the engine is a
systems artifact, not a quality one.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import autotune
from repro.kernels import planned

from .api import EngineBase, Request, validate_request  # noqa: F401
from .api import SPAN_PREFIX, _StreamState, span
from .paged_cache import PagedKVCache
from .scheduler import Scheduler, SchedulerConfig


class PagedServeEngine(EngineBase):
    """Continuous-batching engine over a block-paged KV cache.

    ``max_lanes`` bounds concurrent requests (the decode batch width),
    ``max_seq`` the per-request horizon, ``block_size`` the KV block
    granularity, ``num_blocks`` the shared pool size (default: enough
    for every lane at full horizon — shrink it to oversubscribe and
    exercise preemption).  ``stats`` tracks ``decode_compiles`` (pinned
    at 1 by the tests), ``prefill_compiles`` (one per bucket),
    ``preemptions`` and ``steps``; for an MoE model also
    ``moe_held_assignments``, the (token, held expert) assignments that
    decode steps computed for active lanes, summed over MoE layers (it
    comes back with the sampled tokens: no sync of its own).
    ``decode_kernel_steps`` counts the decode steps whose program
    attends through ``kernels.paged_attention``, and ``decode_kv_rows``
    the K/V rows those steps' kernel read per layer: each active lane's
    ``pos + 1`` (its pooled rows and its new one).
    ``moe_inplace_steps`` counts the decode steps whose MoE layers read
    the held experts in place from the stacked parameters
    (``kernels.held_experts``).  Each
    request counts the prompt rows prefilled for it, real
    (``prefill_tokens``) and as their buckets computed them
    (``prefill_padded_tokens``).

    Each ``step()`` is a profiler step span ``repro/serve.step`` holding
    the spans ``admit`` (with one ``prefill`` per request, carrying its
    ``rid``, ``bucket`` and real ``tokens``, and its ``write_prefill``),
    ``capacity``, ``decode`` and ``sample``.
    """

    def __init__(self, cfg: ModelConfig, *, max_lanes: int = 4,
                 max_seq: int = 512, block_size: int = 16,
                 num_blocks: int | None = None,
                 prompt_len: int | None = None,
                 policy: autotune.PlanPolicy | None = None,
                 scheduler: Scheduler | SchedulerConfig | None = None,
                 target=None, frontend=None):
        super().__init__(cfg, max_seq=max_seq, policy=policy,
                         target=target, frontend=frontend)
        if self.api.paged_decode is None:
            raise ValueError(
                f"family {cfg.family!r} has no paged decode path")
        self.max_lanes = max_lanes
        self.block_size = block_size
        self.num_blocks = num_blocks
        self.prompt_len = prompt_len
        if isinstance(scheduler, SchedulerConfig):
            scheduler = Scheduler(scheduler)
        self.scheduler = scheduler or Scheduler()
        # bucket pads are invisible to masked attention and to dropless
        # MoE routing (pad rows get no expert), but recurrent prompt state
        # (ssm/hybrid) absorbs pad tokens: those families prefill at exact
        # lengths; dense/moe/vlm/encdec bucket.
        self._exact_prefill = cfg.family in ("ssm", "hybrid")
        self.kv: PagedKVCache | None = None
        self.lanes: list[Request | None] = [None] * max_lanes
        self._admit_seq = 0
        self._lane_seq: dict[int, int] = {}
        self._prefill_fns: dict = {}
        self._decode_exec = None
        self._decode_kernel = False
        self._moe_inplace = False
        self.stats = {"decode_compiles": 0, "prefill_compiles": 0,
                      "preemptions": 0, "steps": 0,
                      "decode_kernel_steps": 0, "decode_kv_rows": 0,
                      "moe_inplace_steps": 0}
        if cfg.family == "moe":
            self.stats["moe_held_assignments"] = 0

    # -- load ---------------------------------------------------------------
    def load(self, params):
        """Install weights, build the block pools, and AOT-compile the
        decode executable — exactly once.

        The executable's inputs are all static-shape: tokens
        [max_lanes,1], block_tables [max_lanes, max_seq/block_size],
        pos [max_lanes], active [max_lanes].  Admit/evict/grow edit the
        host-side tables only, so nothing that happens in flight can
        change the compiled shapes — a ``Compiled`` object *errors* on
        aval mismatch instead of retracing, which makes "zero decode
        recompiles" structural rather than aspirational.  Streaming
        chunk feeds write into lane-resident encoder buffers through
        their own jitted updaters — the decode executable is untouched.

        Tracing routes every decode GEMM through ``kernels.planned``
        (one ``best_plan`` per shape, memoized in the mapper's LRU
        cache), so ``step()`` replays the compiled executable with no
        per-step re-planning.  ``plan_report`` keeps only the decisions
        *this warmup* made — a true delta against the process-global
        report, every counter included (planned/fallback, per-backend,
        autotune hit/miss, per-shape).  ``autotune_report`` is the
        crossover-table traffic of the same window; ``measure_calls``
        stays 0, because serve-time planning only *reads* the committed
        table.  The warmup runs under the engine's ``PlanPolicy`` and
        ``target`` when given (``planned.override``).  If
        ``prompt_len`` was given, the bucketed prefill for that length
        is plan-warmed abstractly (no FLOPs).
        """
        self.params = params
        self.kv = PagedKVCache(
            self.api, max_lanes=self.max_lanes, max_seq=self.max_seq,
            block_size=self.block_size, num_blocks=self.num_blocks)
        self.num_blocks = self.kv.num_blocks
        self._decode_kernel = bool(
            self.api.paged_kernel and self.api.paged_kernel(self.kv.pools))
        self._moe_inplace = bool(
            self.api.paged_moe_inplace
            and self.api.paged_moe_inplace(params, self.max_lanes))
        before = planned.planned_report()
        tune0 = autotune.counters()

        def paged_decode_step(p, pools, t, bt, pos, act):
            return self.api.paged_decode(p, pools, t, bt, pos, act)

        with self._plan_ctx():
            # the pools are donated: the step writes its new rows into
            # them in place instead of copying every pool each step
            decode_jit = jax.jit(paged_decode_step, donate_argnums=(1,))
            tokens0 = jnp.zeros((self.max_lanes, 1), jnp.int32)
            bt0, pos0, act0 = self.kv.device_args()
            self._decode_exec = decode_jit.lower(
                params, self.kv.pools, tokens0, bt0, pos0, act0).compile()
            self.stats["decode_compiles"] += 1
            if self.prompt_len:
                bucket = self.scheduler.bucket_for(
                    self.prompt_len, exact=self._exact_prefill)
                li = None if self._exact_prefill else \
                    jax.ShapeDtypeStruct((1,), jnp.int32)
                spec = {"tokens": jax.ShapeDtypeStruct(
                    (1, bucket), jnp.int32)}
                if self.cfg.family == "encdec":
                    spec["frames"] = jax.ShapeDtypeStruct(
                        (1, self.cfg.enc_frames, self.cfg.d_model),
                        jnp.bfloat16)
                if li is None:
                    jax.eval_shape(
                        lambda p, b: self.api.prefill(p, b, bucket),
                        params, spec)
                else:
                    jax.eval_shape(
                        lambda p, b, i: self.api.prefill(
                            p, b, bucket, last_index=i),
                        params, spec, li)
        self.plan_report = planned.report_delta(
            before, planned.planned_report())
        tune1 = autotune.counters()
        self.autotune_report = {k: tune1[k] - tune0[k] for k in tune1}

    # -- admission ----------------------------------------------------------
    def _effective_prompt(self, req: Request) -> np.ndarray:
        """Prompt plus already-generated tokens: a preempted request
        re-prefills its full context and continues where it left off."""
        if not req.output:
            return req.prompt
        return np.concatenate(
            [req.prompt, np.asarray(req.output, np.int32)])

    def _lane_request(self, lane: int) -> Request | None:
        return self.lanes[lane]

    def _append_enc(self, lane: int, ek, ev, start: int,
                    new_len: int) -> None:
        fns = self._stream_fns()
        ck, cv, cl = fns["lane_append"](
            self.kv.pools["enc_k"], self.kv.pools["enc_v"],
            self.kv.pools["enc_len"], ek, ev, lane, start, new_len)
        self.kv.pools = dict(self.kv.pools, enc_k=ck, enc_v=cv,
                             enc_len=cl)

    def _prefill_fn(self, rows: int, batch_keys: tuple, use_li: bool):
        """Jitted prefill producing a ``rows``-deep cache (= bucket
        length, plus patch rows for vlm) — one compile per bucket."""
        key = (rows, batch_keys, use_li)
        fn = self._prefill_fns.get(key)
        if fn is None:
            if use_li:
                def prefill_step(p, b, li):
                    return self.api.prefill(p, b, rows, last_index=li)
            else:
                def prefill_step(p, b):
                    return self.api.prefill(p, b, rows)
            fn = jax.jit(prefill_step)
            self._prefill_fns[key] = fn
            self.stats["prefill_compiles"] += 1
        return fn

    def _stream_prefill_fn(self, rows: int):
        """Jitted decoder-only streaming prefill — one compile per
        bucket, counted in ``prefill_compiles`` like the offline path
        (encdec always buckets, so ``last_index`` is always real)."""
        key = ("stream", rows)
        fn = self._prefill_fns.get(key)
        if fn is None:
            def prefill_step(p, ek, ev, el, tk, li):
                return self.api.stream_prefill(
                    p, ek, ev, el, tk, rows, last_index=li)

            fn = jax.jit(prefill_step)
            self._prefill_fns[key] = fn
            self.stats["prefill_compiles"] += 1
        return fn

    def _admit_one(self, req: Request, lane: int) -> None:
        eff = self._effective_prompt(req)
        plen = len(eff)
        extra_rows = self._extra_rows(req.extra)
        bucket = self.scheduler.bucket_for(plen, exact=self._exact_prefill)
        blocks = self.kv.allocator.alloc(
            self.kv.blocks_for(extra_rows + plen))
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :plen] = eff
        with span("prefill", rid=req.rid, bucket=bucket, tokens=plen):
            if req.t_admit is None:
                req.t_admit = time.perf_counter()
            stream = None
            if req.kind == "audio":
                ck, cv, el, ec, carry = self._stream_admit_state(req)
                fn = self._stream_prefill_fn(bucket)
                logits, pc = fn(self.params, ck, cv, el,
                                jnp.asarray(tokens),
                                jnp.asarray([plen - 1], jnp.int32))
                stream = (ec, carry)
            else:
                batch = {"tokens": jnp.asarray(tokens)}
                if req.extra:
                    batch.update({k: jnp.asarray(v[None])
                                  for k, v in req.extra.items()})
                use_li = not self._exact_prefill
                fn = self._prefill_fn(
                    bucket + extra_rows, tuple(sorted(batch)), use_li)
                if use_li:
                    logits, pc = fn(self.params, batch,
                                    jnp.asarray([plen - 1], jnp.int32))
                else:
                    logits, pc = fn(self.params, batch)
            req.output.append(int(jnp.argmax(logits[0])))
        req.prefill_tokens += plen
        req.prefill_padded_tokens += bucket
        if len(req.output) >= req.max_new_tokens:
            # admit-time done check: the prefill token satisfied the
            # budget — finish without ever occupying a lane
            req.done = True
            self.finished.append(req)
            self.kv.allocator.release(blocks)
            return
        with span("write_prefill"):
            self.kv.install_lane(lane, blocks, extra_rows + plen)
            self.kv.write_prefill(lane, pc)
        self.lanes[lane] = req
        self._lane_seq[lane] = self._admit_seq
        self._admit_seq += 1
        if stream is not None:
            self._streams[lane] = _StreamState(req, *stream)

    def _admit(self) -> None:
        while self.queue:
            free = [i for i, r in enumerate(self.lanes) if r is None]
            n_active = self.max_lanes - len(free)
            needs = [
                self.kv.blocks_for(
                    self._extra_rows(r.extra)
                    + len(self._effective_prompt(r)))
                for r in self.queue
            ]
            n = self.scheduler.plan_admits(
                needs, free_lanes=len(free),
                free_blocks=self.kv.free_blocks(), n_active=n_active)
            if n == 0:
                return
            for _ in range(n):
                req = self.queue.pop(0)
                self._admit_one(req, free.pop(0))
            # a request finishing at admit time frees its lane again:
            # loop so the scheduler can top the step up
            if all(r is not None for r in self.lanes):
                return

    # -- preemption ---------------------------------------------------------
    def _preempt(self, lane: int) -> None:
        req = self.lanes[lane]
        self.kv.release_lane(lane)
        self.lanes[lane] = None
        self._lane_seq.pop(lane, None)
        self._streams.pop(lane, None)
        self.queue.insert(0, req)
        self.stats["preemptions"] += 1

    def _ensure_capacity(self) -> None:
        """Before a decode step: every active lane's next write must fit
        its allocated blocks.  Grow by one block on demand; when the
        pool is dry, preempt the *youngest* active lane (its recompute
        loss is smallest), preferring text lanes over streaming audio
        lanes — an evicted audio request must also replay its consumed
        chunks on re-admission, so its recompute loss is larger.  The
        growing lane itself is only preempted when it is the sole
        active lane left."""
        for lane in range(self.max_lanes):
            while (self.lanes[lane] is not None
                   and int(self.kv.pos[lane])
                   >= self.kv.lane_capacity(lane)):
                if self.kv.free_blocks() > 0:
                    self.kv.grow_lane(lane, self.kv.allocator.alloc(1)[0])
                    continue
                others = [i for i, r in enumerate(self.lanes)
                          if r is not None and i != lane]
                text = [i for i in others
                        if self.lanes[i].kind != "audio"]
                victims = sorted(text or others,
                                 key=lambda i: self._lane_seq.get(i, 0))
                victim = victims[-1] if victims else lane
                self._preempt(victim)
                if victim == lane:
                    break

    # -- step ---------------------------------------------------------------
    def step(self) -> int:
        """Admit + one decode step for all active lanes.  Returns active
        request count after the step plus the queue backlog."""
        with jax.profiler.StepTraceAnnotation(
                SPAN_PREFIX + "step", step_num=self.stats["steps"]):
            return self._step()

    def _step(self) -> int:
        with self._plan_ctx():
            # bucketed prefills compile lazily on first admit, and the
            # streaming chunk feeds trace the encoder GEMMs — the
            # engine's policy/target must be ambient for those traces
            with span("admit"):
                self._admit()
            self._feed_streams()
        with span("capacity"):
            self._ensure_capacity()
        active = [i for i, r in enumerate(self.lanes) if r is not None]
        if not active:
            return len(self.queue)
        with span("decode"):
            self.kv.guard_decode_write()
            tokens = np.zeros((self.max_lanes, 1), np.int32)
            for i in active:
                tokens[i, 0] = self.lanes[i].output[-1]
            bt, pos, act = self.kv.device_args()
            # an MoE model's step also returns its held-expert count
            logits, self.kv.pools, *held = self._decode_exec(
                self.params, self.kv.pools, jnp.asarray(tokens), bt, pos,
                act)
        self.stats["steps"] += 1
        if self._decode_kernel:
            self.stats["decode_kernel_steps"] += 1
            self.stats["decode_kv_rows"] += int(
                self.kv.pos[active].sum()) + len(active)
        if self._moe_inplace:
            self.stats["moe_inplace_steps"] += 1
        with span("sample"):
            nxt, held = jax.device_get((jnp.argmax(logits, axis=-1), held))
            for n in held:
                self.stats["moe_held_assignments"] += int(n)
            for i in active:
                req = self.lanes[i]
                req.output.append(int(nxt[i]))
                self.kv.pos[i] += 1
                if len(req.output) >= req.max_new_tokens:
                    req.done = True
                    self.finished.append(req)
                    self.kv.release_lane(i)
                    self.lanes[i] = None
                    self._lane_seq.pop(i, None)
                    self._streams.pop(i, None)
        return sum(r is not None for r in self.lanes) + len(self.queue)
