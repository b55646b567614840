"""The paged engine's instrumentation: counters and request stamps that
add up, profiler spans that change nothing, named programs, and the
planned sites' scopes in the compiled programs' op metadata."""

import functools
import logging
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.kernels import planned
from repro.models import build_model
from repro.serve import PagedServeEngine

LENS = (5, 9, 20, 3, 14)


@functools.lru_cache(maxsize=None)
def _setup():
    cfg = get_smoke_config("qwen1.5-0.5b")
    return cfg, build_model(cfg).init(jax.random.PRNGKey(7))


def _served(num_blocks=None, max_new=6, block_size=16):
    cfg, params = _setup()
    eng = PagedServeEngine(cfg, max_lanes=3, max_seq=64,
                           block_size=block_size, num_blocks=num_blocks)
    eng.load(params)
    rng = np.random.default_rng(3)
    for n in LENS:
        eng.submit(rng.integers(0, cfg.vocab, n).astype(np.int32),
                   max_new_tokens=max_new)
    done = eng.run_until_drained(4000)
    assert len(done) == len(LENS)
    return eng, sorted(done, key=lambda r: r.rid)


def _op_names(text: str) -> set:
    return set(re.findall(r'op_name="([^"]*)"', text))


def _has_scope(names: set, scope: str) -> bool:
    return any(f"/{scope}/" in n or n.endswith(f"/{scope}") for n in names)


# ---------------------------------------------------------------------------
# counters and stamps
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("num_blocks", [None, 8], ids=["roomy", "preempting"])
def test_request_counters_add_up_over_readmissions(num_blocks):
    # 3 lanes of up to 40 rows in blocks of 8 need 15 blocks; 8 preempt
    eng, reqs = _served(num_blocks, max_new=20, block_size=8)
    preempted = [r for r in reqs if r.prefill_tokens > len(r.prompt)]
    if num_blocks is None:
        assert eng.stats["preemptions"] == 0 and not preempted
        assert [r.prefill_tokens for r in reqs] == list(LENS)
        assert [r.prefill_padded_tokens for r in reqs] == [
            eng.scheduler.bucket_for(n) for n in LENS]
    else:
        # a preempted request prefills again: its prompt and its output
        assert 1 <= len(preempted) <= eng.stats["preemptions"]
    for r in reqs:
        assert r.prefill_tokens >= len(r.prompt)
        assert r.t_admit is not None and r.t_submit <= r.t_admit
        assert r.prefill_tokens <= r.prefill_padded_tokens


def test_queue_wait_grows_behind_full_lanes():
    _, reqs = _served()
    waits = [r.t_admit - r.t_submit for r in reqs]
    # three lanes: the last two requests wait for a lane to free
    assert min(waits[3:]) > max(waits[:3])


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The same requests served plain and under a profile."""
    plain, plain_reqs = _served()
    d = tmp_path_factory.mktemp("profile")
    with jax.profiler.trace(str(d)):
        eng, reqs = _served()
    return plain, plain_reqs, eng, reqs, d


def test_profile_changes_no_counter_and_no_output(traced):
    plain, plain_reqs, eng, reqs, _ = traced
    assert eng.stats == plain.stats
    assert [r.output for r in reqs] == [r.output for r in plain_reqs]
    assert [(r.prefill_tokens, r.prefill_padded_tokens) for r in reqs] == [
        (r.prefill_tokens, r.prefill_padded_tokens) for r in plain_reqs]


def test_profile_records_the_engine_spans(traced):
    from jax.profiler import ProfileData

    _, _, eng, reqs, d = traced
    path = sorted(d.glob("**/*.xplane.pb"))[-1]
    events = [(ev.name, dict(ev.stats))
              for plane in ProfileData.from_file(str(path)).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events
              if ev.name.startswith("repro/serve.")]
    names = {n for n, _ in events}
    assert names == {f"repro/serve.{n}" for n in (
        "step", "admit", "prefill", "write_prefill", "capacity", "decode",
        "sample")}
    steps = [a for n, a in events if n == "repro/serve.step"]
    assert sorted(a["step_num"] for a in steps) == sorted(
        set(a["step_num"] for a in steps))
    prefills = sorted((a["rid"], a["tokens"], a["bucket"])
                      for n, a in events if n == "repro/serve.prefill")
    assert prefills == [(r.rid, len(r.prompt),
                         eng.scheduler.bucket_for(len(r.prompt)))
                        for r in reqs]


# ---------------------------------------------------------------------------
# named programs and scopes
# ---------------------------------------------------------------------------

def test_no_engine_program_compiles_as_a_lambda(caplog):
    cfg, params = _setup()
    with jax.log_compiles(True), caplog.at_level(
            logging.WARNING, logger="jax._src.interpreters.pxla"):
        eng = PagedServeEngine(cfg, max_lanes=2, max_seq=64)
        eng.load(params)
        eng.submit(np.arange(7, dtype=np.int32), max_new_tokens=3)
        eng.run_until_drained()
    compiled = {m.group(1) for r in caplog.records
                for m in [re.match(r"Compiling jit\((\S+?)\) ", r.getMessage())]
                if m}
    assert {"paged_decode_step", "prefill_step", "write_prefill"} <= compiled
    assert not [n for n in compiled if "lambda" in n], compiled


DECODE_SCOPES = ("attn.q", "attn.k", "attn.v", "attn.out",
                 "attn.paged_scores", "attn.paged_values", "mlp.gate",
                 "mlp.up", "mlp.down", "lm_head", "kv.gather", "kv.write")
PREFILL_SCOPES = ("attn.q", "attn.k", "attn.v", "attn.out", "attn.scores",
                  "attn.values", "mlp.gate", "mlp.up", "mlp.down", "lm_head")


def _compiled_text(program: str) -> str:
    cfg, params = _setup()
    eng = PagedServeEngine(cfg, max_lanes=2, max_seq=64)
    eng.load(params)
    if program == "paged_decode_step":
        return eng._decode_exec.as_text()
    fn = eng._prefill_fn(16, ("tokens",), True)
    return fn.lower(params, {"tokens": jnp.zeros((1, 16), jnp.int32)},
                    jnp.zeros((1,), jnp.int32)).compile().as_text()


@pytest.mark.parametrize("program,scopes", [
    ("paged_decode_step", DECODE_SCOPES), ("prefill_step", PREFILL_SCOPES)])
def test_compiled_program_carries_every_site_scope(program, scopes):
    text = _compiled_text(program)
    assert text.startswith(f"HloModule jit_{program}")
    names = _op_names(text)
    assert all(n.startswith(f"jit({program})/") for n in names
               if n.startswith("jit("))
    missing = [s for s in scopes if not _has_scope(names, s)]
    assert not missing, missing
    # planned GEMMs run under their plan's recurrence scope
    assert _has_scope(names, "widesa.mm")


def _facade_call(kind):
    x = jnp.ones((8, 256), jnp.float32)
    if kind == "dense":
        return (lambda a, w: planned.planned_dense(a, w, site="t.dense"),
                (x, jnp.ones((256, 128), jnp.float32)), "t.dense")
    if kind == "bmm":
        return (lambda a, b: planned.planned_bmm(a, b, site="t.bmm"),
                (jnp.ones((2, 8, 128), jnp.float32),
                 jnp.ones((2, 128, 128), jnp.float32)), "t.bmm")
    return (lambda a, wu, bu, wd: planned.planned_mlp_pair(
                a, wu, bu, wd, act="gelu", site="t.pair"),
            (x, jnp.ones((256, 512), jnp.float32),
             jnp.zeros((512,), jnp.float32),
             jnp.ones((512, 256), jnp.float32)), "t.pair")


@pytest.mark.parametrize("enabled", [True, False], ids=["planned", "xla"])
@pytest.mark.parametrize("kind", ["dense", "bmm", "mlp_pair"])
def test_facade_scopes_its_site_on_both_paths(kind, enabled):
    fn, args, site = _facade_call(kind)
    with planned.override(enabled=enabled):
        text = jax.jit(fn).lower(*args).compile().as_text()
    names = _op_names(text)
    assert _has_scope(names, site), sorted(names)
