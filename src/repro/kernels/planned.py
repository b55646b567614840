"""Planned-execution facade: model/serve GEMMs routed through the mapper.

The WideSA claim is that one space-time mapping pipeline — not per-kernel
hand tuning — should pick the tiling for every uniform recurrence.  This
module is where the *application* stack (models/layers.py, serve/engine.py)
cashes that in: ``planned_dense(x, w)`` and ``planned_bmm(a, b)`` normalize
the call-site shapes onto the registered ``mm``/``bmm`` recurrences, build
one ``core.autotune.PlanRequest`` per shape and resolve it through
``core.mapper.best_plan`` (shape-keyed, hitting the existing LRU plan
cache *and* the autotune crossover table per the active ``PlanPolicy``),
then dispatch through the plan's chosen backend (``runtime.execute_plan``
for pallas, the registered XLA lowering when the measured winner is xla).

Configuration is one call (no env-var sprawl):

    planned.configure(enabled=True, policy=PlanPolicy(mode="cached"))
    with planned.override(enabled=False):   # scoped: restores on exit
        ...

Fallback rules (all land on the registry's XLA reference lowering, so the
two paths are interchangeable):

  * planning disabled (``configure(enabled=False)``);
  * dtypes the MXU contract does not cover (or mismatched operand dtypes);
  * shapes the mapper cannot produce a *feasible* plan for (degenerate
    extents, ragged heads, tiny decode dims that defeat the PLIO model).

Both entry points carry a ``jax.custom_vjp`` whose backward GEMMs are
planned through the same facade, so training traffic (value_and_grad
through the model stack) runs on mapper-planned tiles in both directions.

``planned_report()`` exposes per-call-site counters (planned vs fallback,
fallback reasons, the executed backend mix, autotune-table hit/miss, the
plan actually used) so benches and tests can assert which call sites
executed mapper-planned kernels and whether the measured path served
them.  Decisions happen at *trace* time: a jitted model counts once per
compilation, not once per step — which is exactly the "plan once per
shape, execute many" contract.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from repro.core.autotune import PlanPolicy, PlanRequest, resolve
from repro.core.mapper import ExecutionPlan, Target

from . import ref

#: Single-chip execution target for facade call sites.  A 1x8 sub-array is
#: the smallest geometry on which the PLIO/congestion model produces
#: *feasible* plans for the model-stack GEMM shapes (a 1x1 mesh has no
#: column boundary to route over, so everything ranks infeasible).
PLANNED_TARGET = Target(name="planned_chip", mesh_shape=(1, 8))

#: Dtypes the mm/bmm kernel contract covers (see widesa_mm.py / bmm.py).
SUPPORTED_DTYPES = frozenset(
    {"float32", "bfloat16", "int8", "int16", "int32"})

#: Default policy: consult the committed crossover table, never measure
#: at call time (cache misses fall back to the modelled choice).
DEFAULT_POLICY = PlanPolicy(mode="cached")


# ---------------------------------------------------------------------------
# configuration: one configure() call + a scoped override
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PlannedConfig:
    """The facade's whole configuration surface.

    ``target`` is the execution target facade GEMMs plan against: None
    means the single-chip ``PLANNED_TARGET``; a ``core.hierarchy.
    HierarchicalTarget`` makes every facade mm/bmm plan two-level
    (outer Megatron split x inner chip), which is how the serve engines
    turn on tensor parallelism without touching a call site.
    """

    enabled: bool = True
    policy: PlanPolicy = DEFAULT_POLICY
    target: Target | None = None


#: None = configure() never called -> defaults.
_CONFIG: PlannedConfig | None = None

#: configure()/override() sentinel: "leave this field alone" — distinct
#: from None, which for ``target`` means "back to PLANNED_TARGET".
_UNSET = object()


def configure(enabled: bool | None = None,
              policy: PlanPolicy | None = None,
              target=_UNSET) -> PlannedConfig:
    """Set the facade configuration; unspecified fields keep their
    current effective value (``target=None`` explicitly resets to the
    single-chip default).  Returns the new config."""
    global _CONFIG
    base = current_config()
    _CONFIG = PlannedConfig(
        enabled=base.enabled if enabled is None else bool(enabled),
        policy=base.policy if policy is None else policy,
        target=base.target if target is _UNSET else target,
    )
    return _CONFIG


@contextlib.contextmanager
def override(enabled: bool | None = None,
             policy: PlanPolicy | None = None,
             target=_UNSET):
    """Scoped ``configure``: applies inside the ``with`` block, restores
    the previous configuration (including "never configured") on exit."""
    global _CONFIG
    prev = _CONFIG
    try:
        yield configure(enabled=enabled, policy=policy, target=target)
    finally:
        _CONFIG = prev


def reset_configuration() -> None:
    """Back to "never configured" (defaults) — test hook."""
    global _CONFIG
    _CONFIG = None


def current_config() -> PlannedConfig:
    """The effective configuration: explicit ``configure`` wins, else
    the defaults."""
    return _CONFIG if _CONFIG is not None else PlannedConfig()


def planned_enabled() -> bool:
    """Whether the facade plans at all, read at call (= trace) time."""
    return current_config().enabled


def current_policy() -> PlanPolicy:
    return current_config().policy


# ---------------------------------------------------------------------------
# plan lookup: every surface builds the same PlanRequest
# ---------------------------------------------------------------------------

def _norm_dim(d):
    """One request dimension: an int, or (for fused chains) a nested
    per-stage extent tuple."""
    if isinstance(d, (tuple, list)):
        return tuple(int(x) for x in d)
    return int(d)


def plan_request(kind: str, shape, dtype: str,
                 target: Target | None = None,
                 policy: PlanPolicy | None = None) -> PlanRequest:
    """The one way a facade surface describes a plan lookup.  A ``+`` in
    ``kind`` names a fused chain (``mm+mm``); its shape is then a tuple
    of per-stage extent tuples."""
    return PlanRequest(
        kind=kind,
        shape=tuple(_norm_dim(d) for d in shape),
        dtype=str(dtype),
        target=target or current_config().target or PLANNED_TARGET,
        policy=policy or current_policy(),
    )


def plan_for(kind: str, shape, dtype: str,
             target: Target | None = None,
             policy: PlanPolicy | None = None) -> ExecutionPlan | None:
    """Public shape->plan lookup used by benches and tests.  Returns the
    best *feasible* plan (backend-stamped per the policy) or None."""
    return resolve(plan_request(kind, shape, dtype, target, policy))


# ---------------------------------------------------------------------------
# per-call-site report
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SiteStats:
    """Trace-time decision counters for one facade call site."""

    planned: int = 0
    fallback: int = 0
    reasons: dict = dataclasses.field(default_factory=dict)
    backends: dict = dataclasses.field(default_factory=dict)
    autotune: dict = dataclasses.field(
        default_factory=lambda: {"hit": 0, "miss": 0})
    shapes: dict = dataclasses.field(default_factory=dict)
    last_shape: tuple = ()
    last_plan: str = ""

    def as_dict(self) -> dict:
        return {
            "planned": self.planned,
            "fallback": self.fallback,
            "reasons": dict(self.reasons),
            "backends": dict(self.backends),
            "autotune": dict(self.autotune),
            "shapes": dict(self.shapes),
            "last_shape": self.last_shape,
            "last_plan": self.last_plan,
        }


_REPORT: dict[str, SiteStats] = {}


def _record(site: str, shape, *, plan=None, reason=None):
    st = _REPORT.setdefault(site, SiteStats())
    st.last_shape = tuple(shape)
    key = str(tuple(shape))
    st.shapes[key] = st.shapes.get(key, 0) + 1
    if plan is not None:
        st.planned += 1
        st.last_plan = plan.describe()
        st.backends[plan.backend] = st.backends.get(plan.backend, 0) + 1
        bucket = "hit" if plan.provenance == "measured" else "miss"
        st.autotune[bucket] += 1
    else:
        st.fallback += 1
        st.reasons[reason] = st.reasons.get(reason, 0) + 1


def planned_report() -> dict[str, dict]:
    """Snapshot of per-site decisions: {site: {planned, fallback,
    reasons, backends, autotune hit/miss, last plan}}."""
    return {site: st.as_dict() for site, st in sorted(_REPORT.items())}


def planned_report_clear() -> None:
    _REPORT.clear()


def report_delta(before: dict[str, dict],
                 after: dict[str, dict]) -> dict[str, dict]:
    """Difference of two ``planned_report`` snapshots, *every* counter
    delta'd: planned/fallback totals, per-reason and per-backend counts,
    autotune hit/miss, and the per-shape call counts.  Sites with no
    decisions inside the window are dropped; ``last_shape``/``last_plan``
    keep the window-final value (they are states, not counters)."""
    def sub(cur: dict, old: dict) -> dict:
        out = {k: v - old.get(k, 0) for k, v in cur.items()}
        return {k: v for k, v in out.items() if v}

    delta: dict[str, dict] = {}
    for site, st in after.items():
        prev = before.get(site, {})
        d_planned = st["planned"] - prev.get("planned", 0)
        d_fallback = st["fallback"] - prev.get("fallback", 0)
        if not (d_planned or d_fallback):
            continue
        delta[site] = dict(
            st, planned=d_planned, fallback=d_fallback,
            reasons=sub(st["reasons"], prev.get("reasons", {})),
            backends=sub(st["backends"], prev.get("backends", {})),
            autotune={k: st["autotune"][k] - prev.get("autotune", {}).get(
                k, 0) for k in st["autotune"]},
            shapes=sub(st.get("shapes", {}), prev.get("shapes", {})),
        )
    return delta


#: Every (kind, shape, dtype) the facade tried to plan this process —
#: the serving-shape census tools/gen_autotune.py --serving traces
#: (jax.eval_shape through the model stack, then reads this back).
_OBSERVED: set[tuple] = set()


def observed_requests() -> tuple[tuple, ...]:
    """Sorted (kind, shape, dtype) triples the facade has planned (or
    tried to) since the last ``observed_clear``.  Chain kinds carry
    nested per-stage shape tuples."""
    return tuple(sorted(_OBSERVED, key=repr))


def observed_clear() -> None:
    _OBSERVED.clear()


# ---------------------------------------------------------------------------
# decision + dispatch
# ---------------------------------------------------------------------------

def _decide(kind: str, shape: tuple[int, ...], a_dtype, b_dtype):
    """(plan, fallback_reason) for one GEMM call."""
    if not planned_enabled():
        return None, "disabled"
    da, db = jnp.dtype(a_dtype).name, jnp.dtype(b_dtype).name
    if da != db or da not in SUPPORTED_DTYPES:
        return None, f"dtype:{da}x{db}"
    _OBSERVED.add((kind, tuple(shape), da))
    plan = resolve(plan_request(kind, shape, da))
    if plan is None:
        return None, "infeasible"
    return plan, None


def _execute(plan: ExecutionPlan, *operands, out_dtype=None):
    from . import registry  # late: avoids import cycles
    from .runtime import execute_plan

    if hasattr(plan, "outer_split"):  # HierarchicalPlan
        from repro.core import hierarchy

        # facade calls trace under jit (serving AOT-compiles the step),
        # so only the traceable outer compositions run here — a measured
        # chip-backend winner clamps to xla, same as _execute_pair
        backend = plan.backend if plan.backend in ("xla", "pallas") else "xla"
        fn = hierarchy.lower_hierarchical(
            plan, backend=backend, out_dtype=out_dtype)
        return fn(*operands)
    if plan.backend == "xla":
        # the crossover table measured the reference lowering as the
        # winner for this shape — run it, matching the pallas kernels'
        # out_dtype contract (accumulator flush, no operand upcast)
        if plan.recurrence.name == "bmm":
            return _bmm_fallback(*operands, out_dtype)
        out = registry.get(plan.recurrence.name).xla(*operands)
        return out if out_dtype is None else out.astype(out_dtype)
    return execute_plan(plan, *operands, out_dtype=out_dtype)


# -- mm ---------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mm_planned(site: str, x, w):
    m, k = x.shape
    n = w.shape[1]
    plan, _ = _decide("mm", (m, n, k), x.dtype, w.dtype)
    # the caller only enters here when _decide returned a plan; re-deriving
    # it is a pure lru_cache hit, which keeps this function closure-free
    # (custom_vjp primals must not capture tracers)
    return _execute(plan, x, w)


def _mm_planned_fwd(site, x, w):
    return _mm_planned(site, x, w), (x, w)


def _mm_planned_bwd(site, res, g):
    x, w = res
    dx = _dispatch_mm(g, w.T, site + "/bwd_dx")
    dw = _dispatch_mm(x.T, g, site + "/bwd_dw")
    return dx, dw


_mm_planned.defvjp(_mm_planned_fwd, _mm_planned_bwd)


def _dispatch_mm(x, w, site: str):
    m, k = x.shape
    n = w.shape[1]
    plan, reason = _decide("mm", (m, n, k), x.dtype, w.dtype)
    _record(site, (m, n, k), plan=plan, reason=reason)
    with jax.named_scope(site):
        if plan is None:
            return ref.matmul(x, w)
        return _mm_planned(site, x, w)


def planned_dense(x, w, *, site: str = "dense"):
    """``x @ w`` routed through the mapper.

    ``x``: [..., K] (leading dims collapse to the recurrence's M extent);
    ``w``: [K, N].  Returns [..., N] in the dtype the registered mm kernel
    produces (input dtype for floats, int32 for int inputs — identical to
    the XLA reference lowering, so planned and fallback paths agree).
    """
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = w.shape[-1]
    m = int(math.prod(lead)) if lead else 1
    out = _dispatch_mm(x.reshape(m, k), w, site)
    return out.reshape(*lead, n)


# -- bmm --------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _bmm_planned(site: str, out_dtype, a, b):
    nb, m, k = a.shape
    n = b.shape[2]
    plan, _ = _decide("bmm", (nb, m, n, k), a.dtype, b.dtype)
    return _execute(plan, a, b, out_dtype=out_dtype)


def _bmm_planned_fwd(site, out_dtype, a, b):
    return _bmm_planned(site, out_dtype, a, b), (a, b)


def _bmm_planned_bwd(site, out_dtype, res, g):
    a, b = res
    da = _dispatch_bmm(g.astype(a.dtype), b.transpose(0, 2, 1),
                       site + "/bwd_da")
    db = _dispatch_bmm(a.transpose(0, 2, 1), g.astype(b.dtype),
                       site + "/bwd_db")
    return da, db


_bmm_planned.defvjp(_bmm_planned_fwd, _bmm_planned_bwd)


def _bmm_fallback(a, b, out_dtype):
    if out_dtype is None:
        return ref.bmm(a, b)
    if jnp.issubdtype(a.dtype, jnp.integer):
        return ref.bmm(a, b).astype(out_dtype)
    return jnp.einsum("bik,bkj->bij", a, b,
                      preferred_element_type=out_dtype)


def _dispatch_bmm(a, b, site: str, out_dtype=None):
    nb, m, k = a.shape
    n = b.shape[2]
    plan, reason = _decide("bmm", (nb, m, n, k), a.dtype, b.dtype)
    _record(site, (nb, m, n, k), plan=plan, reason=reason)
    with jax.named_scope(site):
        if plan is None:
            return _bmm_fallback(a, b, out_dtype)
        return _bmm_planned(site, out_dtype, a, b)


def planned_bmm(a, b, *, site: str = "bmm", out_dtype=None):
    """Batched ``a @ b`` routed through the mapper.

    ``a``: [..., M, K]; ``b``: [..., K, N] with identical leading batch
    dims (collapsed to the bmm recurrence's batch extent).  Returns
    [..., M, N]; dtype semantics as ``planned_dense``, unless
    ``out_dtype`` asks the kernel to flush its (fp32/int32) accumulator
    at a specific dtype — einsum's ``preferred_element_type``, without
    upcasting the operands (attention scores want fp32 out of bf16
    inputs without materializing an fp32 KV-cache copy).
    """
    batch = a.shape[:-2]
    if b.shape[:-2] != batch:
        raise ValueError(f"batch dims differ: {a.shape} vs {b.shape}")
    nb = int(math.prod(batch)) if batch else 1
    m, k = a.shape[-2:]
    n = b.shape[-1]
    out = _dispatch_bmm(a.reshape(nb, m, k), b.reshape(nb, k, n), site,
                        out_dtype)
    return out.reshape(*batch, m, n)


# -- fused MLP pair (mm+mm chain) -------------------------------------------

#: Interstage activations the fused pair supports — matched to the
#: ``bias_*`` forms in ``core.fusion.INTERSTAGE_OPS``.
_ACT_FNS = {"relu": jax.nn.relu, "silu": jax.nn.silu, "gelu": jax.nn.gelu}


def _pair_shape(m, k, ff, n):
    """Nested mm+mm chain extents for x[m,k] @ wu[k,ff] -> @ wd[ff,n]."""
    return ((m, ff, k), (m, n, ff))


def _decide_pair(m, k, ff, n, dtypes, act: str):
    """(FusedPlan, fallback_reason) for one up->down projection pair."""
    if not planned_enabled():
        return None, "disabled"
    if act not in _ACT_FNS:
        return None, f"act:{act}"
    names = sorted({jnp.dtype(d).name for d in dtypes})
    if len(names) != 1 or names[0] not in SUPPORTED_DTYPES:
        return None, "dtype:" + "x".join(names)
    shape = _pair_shape(m, k, ff, n)
    _OBSERVED.add(("mm+mm", shape, names[0]))
    plan = resolve(plan_request("mm+mm", shape, names[0]))
    if plan is None:
        return None, "infeasible"
    return plan, None


def _execute_pair(plan, act: str, x, wu, bu, wd):
    from repro.core import fusion  # late: core.fusion pulls the registry

    # the resolver fuses the bare chain; the boundary op is a call-site
    # property, stamped here (operand layout follows: x, wu, bias, wd)
    plan = dataclasses.replace(plan, interstage=("bias_" + act,))
    backend = plan.backend if plan.backend in ("xla", "pallas") else "xla"
    return fusion.lower_fused(plan, backend=backend)(x, wu, bu, wd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _mlp_pair_planned(site: str, act: str, x, wu, bu, wd):
    m, k = x.shape
    ff, n = wu.shape[1], wd.shape[1]
    plan, _ = _decide_pair(
        m, k, ff, n, (x.dtype, wu.dtype, bu.dtype, wd.dtype), act)
    # as with _mm_planned: the caller only enters with a fused plan in
    # hand; re-deriving it is a cache hit and keeps the primal closure-free
    return _execute_pair(plan, act, x, wu, bu, wd)


def _mlp_pair_planned_fwd(site, act, x, wu, bu, wd):
    return _mlp_pair_planned(site, act, x, wu, bu, wd), (x, wu, bu, wd)


def _mlp_pair_planned_bwd(site, act, res, g):
    # recompute-in-backward: the fused forward never materialized the
    # intermediate, so the backward re-derives h through planned GEMMs
    x, wu, bu, wd = res
    h_pre = _dispatch_mm(x, wu, site + "/bwd_up") + bu
    h, act_vjp = jax.vjp(_ACT_FNS[act], h_pre)
    dwd = _dispatch_mm(h.T.astype(g.dtype), g, site + "/bwd_dwd")
    dh = _dispatch_mm(g, wd.T.astype(g.dtype), site + "/bwd_dh")
    (dh_pre,) = act_vjp(dh.astype(h_pre.dtype))
    dbu = dh_pre.sum(axis=0).astype(bu.dtype)
    dwu = _dispatch_mm(x.T, dh_pre.astype(x.dtype), site + "/bwd_dwu")
    dx = _dispatch_mm(dh_pre.astype(x.dtype), wu.T, site + "/bwd_dx")
    return dx, dwu, dbu, dwd


_mlp_pair_planned.defvjp(_mlp_pair_planned_fwd, _mlp_pair_planned_bwd)


def planned_mlp_pair(x, wu, bu, wd, *, act: str = "gelu",
                     site: str = "mlp.pair"):
    """The transformer up->bias+activation->down projection pair routed
    through the fusion pass as one ``mm+mm`` chain.

    ``x``: [..., K]; ``wu``: [K, FF]; ``bu``: [FF]; ``wd``: [FF, N].
    When the chain fuses (``core.fusion.fuse`` legality against the
    facade target), both GEMMs run as a single launch with the
    intermediate shard-resident — no HBM round trip between up and down
    projections.  Otherwise falls back to the exact unfused semantics:
    ``planned_dense(x, wu, site="mlp.up")`` + bias + activation, then
    ``planned_dense(..., wd, site="mlp.down")``.
    """
    lead = x.shape[:-1]
    k = x.shape[-1]
    ff, n = wu.shape[-1], wd.shape[-1]
    m = int(math.prod(lead)) if lead else 1
    plan, reason = _decide_pair(
        m, k, ff, n, (x.dtype, wu.dtype, bu.dtype, wd.dtype), act)
    _record(site, _pair_shape(m, k, ff, n), plan=plan, reason=reason)
    with jax.named_scope(site):
        if plan is None:
            act_fn = _ACT_FNS.get(act, jax.nn.gelu)
            h = act_fn(planned_dense(x, wu, site="mlp.up") + bu)
            return planned_dense(h, wd, site="mlp.down")
        out = _mlp_pair_planned(site, act, x.reshape(m, k), wu, bu, wd)
    return out.reshape(*lead, n)


# -- signal-processing frontend (fir / fused fft2d chain / conv2d) ----------
#
# The streaming audio frontend (serve/frontend.py) runs its filter bank,
# FFT tiles, and feature extractor through these — the same
# resolve(plan_request(...)) path as the model GEMMs, with per-site
# report rows — which is how the serving stack proves the "uniform
# recurrences" claim outside GEMM-land.  Inference-only surfaces: no
# custom_vjp (the frontend never trains).

def planned_fir(x, h, *, site: str = "frontend.fir"):
    """1-D FIR filter bank ``y[n] = sum_t x[n+t] * h[t]`` routed through
    the mapper.

    ``x``: [N]; ``h``: [T]; returns [N-T+1] in the registered kernel's
    accumulator dtype (int32 for int inputs, float32 for floats) —
    identical to ``ref.fir``, so planned and fallback paths agree.
    """
    n_out = int(x.shape[-1]) - int(h.shape[-1]) + 1
    taps = int(h.shape[-1])
    plan, reason = _decide("fir", (n_out, taps), x.dtype, h.dtype)
    _record(site, (n_out, taps), plan=plan, reason=reason)
    if plan is None:
        return ref.fir(x, h)
    return _execute(plan, x, h)


def planned_conv2d(img, filt, *, site: str = "frontend.conv2d"):
    """VALID 2-D cross-correlation routed through the mapper.

    ``img``: [H, W]; ``filt``: [P, Q]; returns [H-P+1, W-Q+1] in the
    accumulator dtype (int32 for int inputs, float32 for floats).
    """
    p, q = (int(d) for d in filt.shape)
    oh = int(img.shape[0]) - p + 1
    ow = int(img.shape[1]) - q + 1
    plan, reason = _decide("conv2d", (oh, ow, p, q), img.dtype, filt.dtype)
    _record(site, (oh, ow, p, q), plan=plan, reason=reason)
    if plan is None:
        return ref.conv2d(img, filt)
    return _execute(plan, img, filt)


def _decide_fft2d(rows: int, cols: int, dtypes):
    """(FusedPlan, fallback_reason) for one fft2d stage1->stage2 chain."""
    if not planned_enabled():
        return None, "disabled"
    names = sorted({jnp.dtype(d).name for d in dtypes})
    if names != ["float32"]:
        return None, "dtype:" + "x".join(names)
    shape = ((rows, cols), (rows, cols))
    _OBSERVED.add(("fft2d_stage+fft2d_stage", shape, "float32"))
    plan = resolve(plan_request("fft2d_stage+fft2d_stage", shape, "float32"))
    if plan is None:
        return None, "infeasible"
    return plan, None


def planned_fft2d(x_re, x_im, *, site: str = "frontend.fft2d"):
    """Whole 2-D FFT of one [rows, cols] tile, planned as the fused
    ``fft2d_stage+fft2d_stage`` chain (row pass -> column pass sharing
    one pre-skew, intermediate shard-resident — see docs/fusion.md).

    ``x_re``/``x_im``: float32 [rows, cols] planes; returns the
    ``(real, imag)`` float32 pair, identical to ``ref.fft2d``.
    """
    from repro.core import fusion  # late: core.fusion pulls the registry

    rows, cols = (int(d) for d in x_re.shape)
    plan, reason = _decide_fft2d(rows, cols, (x_re.dtype, x_im.dtype))
    _record(site, ((rows, cols), (rows, cols)), plan=plan, reason=reason)
    if plan is None:
        return ref.fft2d(x_re, x_im)
    backend = plan.backend if plan.backend in ("xla", "pallas") else "xla"
    return fusion.lower_fused(plan, backend=backend)(x_re, x_im)
