"""Driver kind ``serve``: requests through the program's paged serving
engine (``serve.make_engine(kind="paged")``), driven step by step on the
real clock by a closed or open loop.

Cell settings (``chipbench/workloads/<cell>.json``):

  lanes, max_seq, block_size   the engine's decode width, horizon, blocks
  prefill_buckets              the engine scheduler's prefill lengths
  warm_steps                   closed loop: steps run before the window
                               opens, so that it starts in steady state
  drain_seconds                after the window, how long requests that
                               arrived in it may take to finish
  trace_seconds                length of the traced part of the window
  check_tokens                 served tokens the reference compares
  check_pad                    fixed sequence length of the reference
  limit                        the comparison's limit (see PERF.md)

Timing: a request is due at its scheduled arrival (open loop) or when its
client submits it (closed loop).  Every token a step emits is stamped
with the end of that step, after the engine's own host sync.  Time to
first token runs from due to that stamp; the gaps between tokens are the
differences of consecutive stamps.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from chipbench import harness, trace, traffic, weights, work
from chipbench.reference import qwen as ref

#: published config.json key -> the program's ModelConfig field
HF_TO_PROGRAM = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings", "hidden_act": "act",
    "torch_dtype": "dtype", "head_dim": "head_dim",
}


def model_config(config: dict):
    """The program's ModelConfig for a configuration file: the published
    keys mapped by name, plus the file's ``program`` fields (what the
    published config implies but does not state, such as the family)."""
    from repro.configs.base import ModelConfig

    kw = {field: config[key] for key, field in HF_TO_PROGRAM.items()
          if key in config}
    kw["kv_cache_dtype"] = config["torch_dtype"]
    kw.update(config.get("program", {}))
    return ModelConfig(name=config["name"], **kw)


def engine(config: dict, s: dict):
    """The program's paged engine with a cell's settings, and the shapes
    of its parameters."""
    from repro.serve import make_engine
    from repro.serve.scheduler import SchedulerConfig

    eng = make_engine(
        model_config(config), kind="paged", max_lanes=s["lanes"],
        max_seq=s["max_seq"], block_size=s["block_size"],
        scheduler=SchedulerConfig(prefill_buckets=tuple(s["prefill_buckets"])))
    return eng, jax.eval_shape(eng.api.init, jax.random.PRNGKey(0))


@dataclasses.dataclass
class Track:
    req: object          # the engine's Request
    plen: int
    due: float
    in_window: bool
    client: int = 0
    seen: int = 0
    times: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class StepRec:
    t0: float
    t1: float
    traced: bool
    prefills: list = dataclasses.field(default_factory=list)   # prompt lens
    decode_ctx: list = dataclasses.field(default_factory=list)  # keys read


class Loop:
    """The engine, the requests in flight, and per-step accounting."""

    def __init__(self, eng, spans, vocab: int, seed: int):
        self.eng = eng
        self.spans = spans
        self.live: list[Track] = []
        self.tracks: list[Track] = []
        self.steps: list[StepRec] = []
        self.next_prompt = traffic.prompt_source(seed, vocab)

    def submit(self, spec: traffic.Spec, due: float, in_window: bool):
        with self.spans.span("submit"):
            self.eng.submit(self.next_prompt(spec.prompt_len),
                            max_new_tokens=spec.output_len)
        tr = Track(self.eng.queue[-1], spec.prompt_len, due, in_window,
                   spec.client)
        self.live.append(tr)
        self.tracks.append(tr)
        return tr

    def busy(self) -> bool:
        return bool(self.eng.queue) or any(
            r is not None for r in self.eng.lanes)

    def step(self) -> list[Track]:
        """One engine step; returns the requests it finished."""
        rec = StepRec(harness.now(), 0.0, self.spans.tracing)
        with self.spans.span("step"):
            self.eng.step()
        rec.t1 = t = harness.now()
        done = []
        with self.spans.span("account"):
            for tr in self.live:
                n = len(tr.req.output)
                d = n - tr.seen
                if d == 0:
                    continue
                if tr.seen == 0:
                    rec.prefills.append(tr.plen)
                    before, dec = 1, d - 1
                else:
                    before, dec = tr.seen, d
                rec.decode_ctx.extend(tr.plen + before + j
                                      for j in range(dec))
                tr.times.extend([t] * d)
                tr.seen = n
                if tr.req.done:
                    done.append(tr)
            if done:
                self.live = [tr for tr in self.live if not tr.req.done]
        self.steps.append(rec)
        return done


def _warm(eng, mix: dict, next_prompt) -> None:
    """Compile every program the cell's traffic uses: one request per
    prefill bucket its prompt lengths fall in, each through a prefill
    and a decode step."""
    lo, hi = mix["prompt_len"]
    buckets = sorted({eng.scheduler.bucket_for(n) for n in (lo, hi)}
                     | {b for b in eng.scheduler.config.prefill_buckets
                        if lo <= b <= hi})
    for b in buckets:
        eng.submit(next_prompt(max(lo, min(b, hi))), max_new_tokens=2)
    eng.run_until_drained()
    eng.finished.clear()


def _closed(loop: Loop, mix, seed, t_window, seconds, warm_steps, drain,
            tracer):
    queues = traffic.closed_loop(mix, seed)
    nxt = [0] * len(queues)

    def submit(c, due, in_window):
        spec = queues[c][nxt[c] % len(queues[c])]
        nxt[c] += 1
        loop.submit(spec, due, in_window)

    t = harness.now()
    for c in range(len(queues)):
        submit(c, t, False)
    for _ in range(warm_steps):
        for tr in loop.step():
            submit(tr.client, loop.steps[-1].t1, False)
    t0 = t_window()
    tracer.start(until=t0 + min(seconds, tracer.seconds))
    deadline = t0 + seconds
    while True:
        done = loop.step()
        t = loop.steps[-1].t1
        tracer.maybe_stop(t)
        for tr in done:
            if t < deadline:
                submit(tr.client, t, True)
        if t >= deadline and not any(tr.in_window for tr in loop.live):
            break
        if t >= deadline + drain:
            break
    return t0


def _open(loop: Loop, mix, t_window, seconds, drain, tracer):
    specs = traffic.open_loop(mix, seconds)
    t0 = t_window()
    tracer.start(until=t0 + min(seconds, tracer.seconds))
    i = 0
    while True:
        t = harness.now()
        while i < len(specs) and t0 + specs[i].arrival_s <= t:
            loop.submit(specs[i], t0 + specs[i].arrival_s, True)
            i += 1
        if loop.busy():
            loop.step()
            t = loop.steps[-1].t1
        elif i < len(specs):
            with loop.spans.span("wait"):
                time.sleep(max(0.0, t0 + specs[i].arrival_s
                               - harness.now()))
            t = harness.now()
        else:
            break
        tracer.maybe_stop(t)
        if t >= t0 + seconds + drain:
            break
    return t0


def _check(tracks, params, config, settings, mix, seed, quant=None):
    """Widest gap by which a served token's reference logit lies below
    the reference's best, over a sample of finished requests drawn from
    the seed with the longest among them.  With ``quant`` the reading is
    the control's: the gap of the token that the lower precision puts
    first, at the same positions."""
    done = [tr for tr in tracks if tr.in_window and tr.req.done]
    if not done:
        return float("nan"), 0
    longest = max(done, key=lambda tr: tr.plen + len(tr.req.output))
    rest = [tr for tr in done if tr is not longest]
    order = traffic.rng_for(seed, "check").permutation(len(rest))
    sample, tokens = [longest], len(longest.req.output)
    for j in order:
        if tokens >= settings["check_tokens"]:
            break
        sample.append(rest[j])
        tokens += len(rest[j].req.output)
    pad = settings["check_pad"]
    n_sel = mix["output_len"][1]    # one compile for every sample
    widest = 0.0
    for tr in sample:
        out = np.asarray(tr.req.output, np.int32)
        seq = np.concatenate([tr.req.prompt, out[:-1]])
        sel = tr.plen - 1 + np.arange(len(out))
        toks = np.zeros(pad, np.int32)
        toks[:len(seq)] = seq
        sel_pad = np.zeros(n_sel, np.int32)
        sel_pad[:len(sel)] = sel
        want = ref.logits(params, config, toks, sel_pad)[:len(sel)]
        if quant is None:
            picked = out
        else:
            low = ref.logits(params, config, toks, sel_pad,
                             quant=quant)[:len(sel)]
            picked = np.asarray(low.argmax(-1))
        want = np.asarray(want)
        best = want.max(-1)
        gap = best - want[np.arange(len(sel)), picked]
        if not np.all(np.isfinite(gap)):
            return float("nan"), tokens
        widest = max(widest, float(gap.max()))
    return widest, tokens


def run(ctx: harness.Context):
    config, s, mix = ctx.cell.config, ctx.cell.settings, ctx.cell.traffic
    devs = ctx.devices
    eng, shapes = engine(config, s)
    params = weights.make(shapes, ctx.seed, config["hidden_size"])
    eng.load(params)
    spans = harness.Spans()
    loop = Loop(eng, spans, config["vocab_size"], ctx.seed)
    _warm(eng, mix, traffic.prompt_source(ctx.seed + 1, config["vocab_size"]))
    counter = harness.CompileCounter()
    stats0, c0 = dict(eng.stats), counter.snapshot()

    tracer = trace.Session(ctx.trace, spans, s["trace_seconds"],
                           keep=ctx.keep_trace)
    marks = {}

    def t_window():
        marks["t0"] = harness.now()
        return marks["t0"]

    if mix["kind"] == "closed":
        _closed(loop, mix, ctx.seed, t_window, ctx.seconds, s["warm_steps"],
                s["drain_seconds"], tracer)
    else:
        _open(loop, mix, t_window, ctx.seconds, s["drain_seconds"], tracer)
    t0 = marks["t0"]
    reduced = tracer.stop(devs)
    stats1, c1 = dict(eng.stats), counter.snapshot()
    harness.log(f"engine stats before window {stats0}, after {stats1}; "
                f"programs traced/compiled before {c0}, after {c1}")
    mem = harness.memory_peak_bytes(devs)
    tracks = loop.tracks
    in_win = [tr for tr in tracks if tr.in_window]
    failed = sum(1 for tr in in_win if not tr.req.done)
    harness.log(f"window: {len(in_win)} requests arrived, {failed} "
                f"unfinished after the drain, {len(loop.steps)} steps")
    del eng, loop.eng
    gap, n_tok = _check(tracks, params, config, s, mix, ctx.seed)
    harness.log(f"check: widest served-token logit gap {gap!r} over "
                f"{n_tok} served tokens")
    lm = work.LM.from_config(config)
    return harness.Run(
        setup_s=t0 - ctx.t_start, window_s=ctx.seconds,
        attempted=len(in_win), failed=failed,
        checks=[harness.Check("served_logit_gap", gap, s["limit"])],
        memory_peak_bytes=mem, peaks=ctx.peaks, chips=len(devs), trace=reduced,
        data={"t0": t0, "tracks": tracks, "steps": loop.steps, "lm": lm,
              "lanes": s["lanes"]})
