"""Recurrence-generic KernelSpec registry (mapper -> runtime -> codegen).

The paper's point is a mapping scheme for *uniform recurrences in
general*; this module is where the execution stack learns about one.  A
``KernelSpec`` declares, in one place, everything the layers downstream
of the mapper need:

    arity          operand count of ``execute_plan``
    grid_loops     IR loop (or fused-loop tuple) per kernel grid dim —
                   combined with the recurrence's reduction loops this
                   yields the Pallas dimension semantics
    block_kwargs   Partition -> kernel tile kwargs (the plan contract)
    pallas         the Pallas lowering (an ops.py staging wrapper)
    xla            the XLA reference lowering (a ref.py oracle)
    builder        the IR builder in core/recurrence.py
    operands       (recurrence, rng) -> sample operands matching its
                   extents (tests / benches / smoke all draw from here);
                   ``rng`` is a numpy Generator or a ``DeviceRng``
    systolic_lowering
                   chip-level neighbour-stream schedule hook,
                   ``(plan, mesh) -> Callable(*operands)`` — the
                   ``lower_plan(..., backend="systolic")`` dispatch target
                   (``kernels/systolic.py``); None = not supported
    allgather_lowering
                   the GSPMD all-gather/broadcast baseline hook for the
                   same backend surface (``backend="allgather"``)
    supports_systolic (property)
                   True iff a ``systolic_lowering`` hook is registered
    fusable_with   producer names this spec may *consume* in a fused
                   chain (``core/fusion.py``): stage ``i``'s name must
                   appear in stage ``i+1``'s ``fusable_with`` or the
                   chain is rejected (spec-author contract:
                   docs/fusion.md)
    fused_systolic_lowering
                   chain-level one-shard_map schedule hook,
                   ``(fused_plan, mesh) -> Callable(*chain_operands)``
                   — the ``fused_systolic`` backend dispatch target,
                   looked up on the chain's *last* (consumer) spec
    n_outputs      how many leading operands of a downstream consumer
                   this spec's output covers in a chain (the two-plane
                   complex fft stage feeds (re, im) = 2)
    parity_dtypes  dtypes the backend-parity suite sweeps
    atol           float comparison tolerance for parity (ints are exact)
    smoke_args     reduced builder sizes for smoke runs
    bench_cases    (dtype, builder args) table rows for the benchmark —
                   these double as the autotune crossover-table keys
                   (``autotune_cases``/``core/autotune.py``): the
                   committed default table covers every case here

``kernels/runtime.py`` (execute_plan), ``core/codegen.py`` (all four
backends), ``benchmarks/bench_recurrences.py`` and the parity tests are
pure registry lookups — adding a workload is one builder plus one
``register(...)`` call here, not a four-file shotgun edit.
"""

from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, Any, Callable

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import recurrence as ir
from repro.core.partition import MXU_LANES

from . import ref
from . import systolic as chip

if TYPE_CHECKING:  # pragma: no cover - typing only, avoids import cycles
    from repro.core.mapper import ExecutionPlan
    from repro.core.recurrence import UniformRecurrence


class UnregisteredRecurrenceError(NotImplementedError):
    """Raised when a plan names a recurrence with no registered KernelSpec."""

    def __init__(self, name: str):
        super().__init__(
            f"no KernelSpec registered for recurrence {name!r}; "
            f"registered: {registered_names()}. Add a builder in "
            "core/recurrence.py and a register(KernelSpec(...)) entry in "
            "kernels/registry.py (README: 'Adding a new recurrence')."
        )
        self.name = name


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """Declarative per-recurrence execution contract (module docstring)."""

    name: str
    arity: int
    grid_loops: tuple[Any, ...]
    block_kwargs: Callable[["ExecutionPlan"], dict]
    pallas: Callable[..., Any]
    xla: Callable[..., Any]
    builder: Callable[..., "UniformRecurrence"]
    operands: Callable[..., tuple]
    systolic_lowering: Callable[..., Callable] | None = None
    allgather_lowering: Callable[..., Callable] | None = None
    fusable_with: tuple[str, ...] = ()
    fused_systolic_lowering: Callable[..., Callable] | None = None
    n_outputs: int = 1
    parity_dtypes: tuple[str, ...] = ("float32", "int8", "int16")
    atol: float = 1e-3
    smoke_args: tuple[int, ...] = ()
    bench_cases: tuple[tuple[str, tuple[int, ...]], ...] = ()

    @property
    def supports_systolic(self) -> bool:
        """Whether a chip-level neighbour-stream schedule is registered."""
        return self.systolic_lowering is not None


_REGISTRY: dict[str, KernelSpec] = {}


def register(spec: KernelSpec) -> KernelSpec:
    if spec.name in _REGISTRY:
        raise ValueError(f"KernelSpec {spec.name!r} already registered")
    _REGISTRY[spec.name] = spec
    return spec


def get(name: str) -> KernelSpec:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise UnregisteredRecurrenceError(name) from None


def registered_names() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def specs() -> tuple[KernelSpec, ...]:
    return tuple(_REGISTRY[n] for n in registered_names())


def autotune_cases(spec: KernelSpec) -> tuple[tuple[str, tuple[int, ...]], ...]:
    """The (dtype, builder-args) cases a crossover table must cover for
    ``spec``: the smoke case (what ``benchmarks/run.py --ci`` plans) plus
    every bench case (the paper-scale Table III sizes) — bench sizes
    double as autotune keys."""
    return ((spec.parity_dtypes[0], spec.smoke_args), *spec.bench_cases)


# ---------------------------------------------------------------------------
# built-in specs
# ---------------------------------------------------------------------------

def _ops(fname: str) -> Callable[..., Any]:
    """Lazy dispatcher onto an ops.py staging wrapper — ops imports the
    kernel modules importing runtime importing us, so the lookup resolves
    at call time (exactly like runtime.execute_plan used to)."""

    def call(*a, **kw):
        from . import ops

        return getattr(ops, fname)(*a, **kw)

    return call


class DeviceRng:
    """The two numpy ``Generator`` draws ``KernelSpec.operands`` makes,
    made on the device from one PRNG key instead — traceable, so a
    jitted ``spec.operands(rec, DeviceRng(key))`` builds bench-size
    operands in device memory (and ``jax.eval_shape`` of it gives their
    shapes without allocating)."""

    def __init__(self, key):
        self._key = key

    def _next(self):
        self._key, key = jax.random.split(self._key)
        return key

    def integers(self, low: int, high: int, shape):
        return jax.random.randint(self._next(), tuple(shape), low, high,
                                  dtype=jnp.int32)

    def standard_normal(self, shape):
        return jax.random.normal(self._next(), tuple(shape), jnp.float32)


def _draw(rng, shape, dtype: str):
    """Sample one operand; complex dtypes lower to float32 real planes."""
    if dtype.startswith("int"):
        return jnp.asarray(rng.integers(-8, 8, shape).astype(dtype))
    return jnp.asarray(rng.standard_normal(shape).astype(np.float32))


def _mm_blocks(plan: "ExecutionPlan") -> dict:
    blk = plan.partition.block
    return {
        "bm": blk.get("i", MXU_LANES),
        "bn": blk.get("j", MXU_LANES),
        "bk": blk.get("k", MXU_LANES),
    }


def _mm_operands(rec: "UniformRecurrence", rng) -> tuple:
    m, n, k = (rec.extent(l) for l in ("i", "j", "k"))
    d = rec.dtype
    return _draw(rng, (m, k), d), _draw(rng, (k, n), d)


register(KernelSpec(
    name="mm",
    arity=2,
    grid_loops=("i", "j", "k"),
    block_kwargs=_mm_blocks,
    pallas=_ops("matmul"),
    xla=ref.matmul,
    builder=ir.matmul,
    operands=_mm_operands,
    systolic_lowering=chip.cannon_mm,
    allgather_lowering=chip.allgather_mm,
    fusable_with=("mm",),
    fused_systolic_lowering=chip.fused_cannon_mm,
    smoke_args=(256, 256, 256),
    bench_cases=(
        ("float32", (8192, 8192, 8192)),
        ("int8", (10240, 10240, 10240)),
        ("int16", (9600, 9600, 9600)),
        ("int32", (8192, 8192, 8192)),
    ),
))


def _fft_operands(rec: "UniformRecurrence", rng) -> tuple:
    r, c = rec.extent("i"), rec.extent("j")
    return _draw(rng, (r, c), "float32"), _draw(rng, (r, c), "float32")


register(KernelSpec(
    name="fft2d_stage",
    arity=2,
    grid_loops=("i", "j", "k"),
    block_kwargs=_mm_blocks,
    pallas=_ops("fft2d"),
    xla=ref.fft2d,
    builder=ir.fft2d_stage,
    # complex data rides as two float32 real planes on the MXU; int DFT
    # matrices do not exist, so parity runs the float planes only
    parity_dtypes=("float32",),
    atol=1.0,
    operands=_fft_operands,
    systolic_lowering=chip.cannon_fft2d,
    allgather_lowering=chip.allgather_fft2d,
    fusable_with=("fft2d_stage",),
    fused_systolic_lowering=chip.fused_cannon_fft2d,
    n_outputs=2,
    smoke_args=(64, 64),
    bench_cases=(("cfloat", (8192, 8192)), ("cint16", (8192, 8192))),
))


def _conv_blocks(plan: "ExecutionPlan") -> dict:
    blk = plan.partition.block
    return {
        "bh": blk.get("h", MXU_LANES),
        "bw": blk.get("w", MXU_LANES),
    }


def _conv_operands(rec: "UniformRecurrence", rng) -> tuple:
    h, w, p, q = (rec.extent(l) for l in ("h", "w", "p", "q"))
    d = rec.dtype
    return _draw(rng, (h + p - 1, w + q - 1), d), _draw(rng, (p, q), d)


register(KernelSpec(
    name="conv2d",
    arity=2,
    grid_loops=("h", "w", ("p", "q")),
    block_kwargs=_conv_blocks,
    pallas=_ops("conv2d"),
    xla=ref.conv2d,
    builder=ir.conv2d,
    operands=_conv_operands,
    systolic_lowering=chip.chain_conv2d,
    allgather_lowering=chip.allgather_conv2d,
    fusable_with=("conv2d",),
    fused_systolic_lowering=chip.fused_halo_chain,
    # output rows divide the linearized chain of the parity meshes (2x2
    # and 2x4); width stays odd to keep the staging padding exercised
    smoke_args=(64, 61, 4, 4),
    bench_cases=(
        ("float32", (10240, 10240, 4, 4)),
        ("int8", (10240, 10240, 8, 8)),
        ("int16", (10240, 10240, 4, 4)),
        ("int32", (10240, 10240, 4, 4)),
    ),
))


def _fir_blocks(plan: "ExecutionPlan") -> dict:
    return {"bn": plan.partition.block.get("n", 1024)}


def _fir_operands(rec: "UniformRecurrence", rng) -> tuple:
    n, t = rec.extent("n"), rec.extent("t")
    d = rec.dtype
    return _draw(rng, (n + t - 1,), d), _draw(rng, (t,), d)


register(KernelSpec(
    name="fir",
    arity=2,
    grid_loops=("n",),
    block_kwargs=_fir_blocks,
    pallas=_ops("fir"),
    xla=ref.fir,
    builder=ir.fir,
    operands=_fir_operands,
    systolic_lowering=chip.chain_fir,
    allgather_lowering=chip.allgather_fir,
    # output count divides the linearized chain of the parity meshes
    smoke_args=(1024, 15),
    bench_cases=(
        ("float32", (1048576, 15)),
        ("int8", (1048576, 15)),
        ("int16", (1048576, 15)),
        ("cfloat", (1048576, 15)),
    ),
))


def _bmm_operands(rec: "UniformRecurrence", rng) -> tuple:
    b, m, n, k = (rec.extent(l) for l in ("b", "i", "j", "k"))
    d = rec.dtype
    return _draw(rng, (b, m, k), d), _draw(rng, (b, k, n), d)


register(KernelSpec(
    name="bmm",
    arity=2,
    grid_loops=("b", "i", "j", "k"),
    block_kwargs=_mm_blocks,
    pallas=_ops("bmm"),
    xla=ref.bmm,
    builder=ir.batched_matmul,
    operands=_bmm_operands,
    systolic_lowering=chip.cannon_bmm,
    allgather_lowering=chip.allgather_bmm,
    smoke_args=(4, 128, 128, 64),
    bench_cases=(
        ("float32", (64, 4096, 4096, 4096)),
        ("int8", (64, 4096, 4096, 4096)),
        ("int16", (64, 4096, 4096, 4096)),
    ),
))


def _jacobi_blocks(plan: "ExecutionPlan") -> dict:
    blk = plan.partition.block
    return {
        "bh": blk.get("i", MXU_LANES),
        "bw": blk.get("j", MXU_LANES),
    }


def _jacobi_operands(rec: "UniformRecurrence", rng) -> tuple:
    h, w = rec.extent("i"), rec.extent("j")
    d = rec.dtype
    return (
        _draw(rng, (h + 2, w + 2), d),
        _draw(rng, (len(ir.JACOBI2D_OFFSETS),), d),
    )


register(KernelSpec(
    name="jacobi2d",
    arity=2,
    # the dedicated stencil kernel (kernels/jacobi2d.py) contracts all 5
    # star planes in one visit: the reduction loop s never reaches the grid
    grid_loops=("i", "j"),
    block_kwargs=_jacobi_blocks,
    pallas=_ops("jacobi2d"),
    xla=ref.jacobi2d,
    builder=ir.jacobi2d,
    operands=_jacobi_operands,
    systolic_lowering=chip.halo_stencil,
    allgather_lowering=chip.allgather_stencil,
    fusable_with=("conv2d", "jacobi2d", "jacobi2d_9pt"),
    fused_systolic_lowering=chip.fused_halo_chain,
    smoke_args=(126, 126),
    bench_cases=(
        ("float32", (10238, 10238)),
        ("int8", (10238, 10238)),
        ("int16", (10238, 10238)),
    ),
))


def _jacobi_ms_operands(rec: "UniformRecurrence", rng) -> tuple:
    h, w, t = rec.extent("i"), rec.extent("j"), rec.extent("t")
    d = rec.dtype
    return (
        _draw(rng, (h + 2, w + 2), d),
        _draw(rng, (t, len(ir.JACOBI2D_OFFSETS)), d),
    )


register(KernelSpec(
    name="jacobi2d_ms",
    arity=2,
    # the sweep loop t is a host-level loop around the stencil kernel (its
    # flow dependence forbids both space mapping and grid parallelism);
    # the per-sweep weights W[t, s] carry the sweep count in-operand
    grid_loops=("i", "j"),
    block_kwargs=_jacobi_blocks,
    pallas=_ops("jacobi2d_ms"),
    xla=ref.jacobi2d_ms,
    builder=ir.jacobi2d_multisweep,
    operands=_jacobi_ms_operands,
    systolic_lowering=chip.halo_stencil,
    allgather_lowering=chip.allgather_stencil,
    smoke_args=(62, 62, 3),
    bench_cases=(
        ("float32", (4094, 4094, 8)),
        ("int8", (4094, 4094, 8)),
        ("int16", (4094, 4094, 8)),
    ),
))


def _jacobi9_operands(rec: "UniformRecurrence", rng) -> tuple:
    h, w = rec.extent("i"), rec.extent("j")
    d = rec.dtype
    return (
        _draw(rng, (h + 4, w + 4), d),
        _draw(rng, (len(ir.JACOBI2D_9PT_OFFSETS),), d),
    )


register(KernelSpec(
    name="jacobi2d_9pt",
    arity=2,
    # radius-2 star: same single-visit stencil kernel (plane-count
    # generic), 9 shifted planes staged by ops.jacobi2d_9pt
    grid_loops=("i", "j"),
    block_kwargs=_jacobi_blocks,
    pallas=_ops("jacobi2d_9pt"),
    xla=ref.jacobi2d_9pt,
    builder=ir.jacobi2d_9pt,
    operands=_jacobi9_operands,
    systolic_lowering=chip.halo_stencil,
    allgather_lowering=chip.allgather_stencil,
    fusable_with=("conv2d", "jacobi2d", "jacobi2d_9pt"),
    fused_systolic_lowering=chip.fused_halo_chain,
    smoke_args=(64, 64),
    bench_cases=(
        ("float32", (10236, 10236)),
        ("int8", (10236, 10236)),
        ("int16", (10236, 10236)),
    ),
))


def _mttkrp_blocks(plan: "ExecutionPlan") -> dict:
    blk = plan.partition.block
    return {
        "bi": blk.get("i", MXU_LANES),
        "bj": blk.get("j", MXU_LANES),
        "bk": blk.get("k", 16),
        "bl": blk.get("l", 16),
    }


def _mttkrp_operands(rec: "UniformRecurrence", rng) -> tuple:
    i, j, k, l = (rec.extent(x) for x in ("i", "j", "k", "l"))  # noqa: E741
    d = rec.dtype
    return (
        _draw(rng, (i, k, l), d),
        _draw(rng, (k, j), d),
        _draw(rng, (l, j), d),
    )


register(KernelSpec(
    name="mttkrp",
    arity=3,
    grid_loops=("i", "j", "k", "l"),
    block_kwargs=_mttkrp_blocks,
    pallas=_ops("mttkrp"),
    xla=ref.mttkrp,
    builder=ir.mttkrp,
    operands=_mttkrp_operands,
    systolic_lowering=chip.ring_mttkrp,
    allgather_lowering=chip.allgather_mttkrp,
    smoke_args=(128, 64, 16, 8),
    bench_cases=(
        ("float32", (4096, 400, 256, 256)),
        ("int8", (4096, 400, 256, 256)),
        ("int16", (4096, 400, 256, 256)),
    ),
))
