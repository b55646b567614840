"""Block-paged KV cache: fixed-size blocks on the sequence axis, per-lane
block tables, host-side alloc/free.

The device state is a set of *block pools* — ``paged`` leaves shaped
[L, num_blocks, block_size, ...] shared by every request — plus ``lane``
leaves ([L, max_lanes, ...]) for states that are per-request but fixed
size (SSM/conv recurrent state, encoder K/V) and ``lane_scalar`` leaves
([max_lanes] — one scalar per request, e.g. the streaming ``enc_len``
frame count).  Which leaf is which comes from the model family's
``paged_layout()``.

Everything *about* the blocks lives on the host: the free list, each
lane's block list, the [max_lanes, blocks_per_lane] int32 block tables,
per-lane ``pos`` and the ``active`` mask.  Admitting, growing, or
freeing a request edits these host arrays only — the decode executable
always sees the same static shapes, so join/evict never recompiles.

Freeing is O(1) per block and never touches other lanes' device data:
freed blocks simply return to the free list; their stale contents are
masked by ``kpos <= pos`` until a future write overwrites them (the
same trick a contiguous cache plays with its zero tail).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


class BlockAllocator:
    """Host-side free list over ``num_blocks`` pool blocks."""

    def __init__(self, num_blocks: int):
        self.num_blocks = num_blocks
        self._free = list(range(num_blocks))

    @property
    def free(self) -> int:
        return len(self._free)

    def alloc(self, n: int) -> list[int]:
        if n > len(self._free):
            raise MemoryError(
                f"paged cache exhausted: need {n} blocks, "
                f"{len(self._free)} free of {self.num_blocks}")
        out = self._free[:n]
        del self._free[:n]
        return out

    def release(self, blocks: list[int]) -> None:
        self._free.extend(blocks)


class PagedKVCache:
    """Device block pools + host block tables for one model family."""

    def __init__(self, api, *, max_lanes: int, max_seq: int,
                 block_size: int, num_blocks: int | None = None):
        if max_seq % block_size:
            raise ValueError(
                f"max_seq={max_seq} must be a multiple of "
                f"block_size={block_size} (the block table is "
                "max_seq/block_size entries wide)")
        self.api = api
        self.max_lanes = max_lanes
        self.max_seq = max_seq
        self.block_size = block_size
        self.blocks_per_lane = max_seq // block_size
        if num_blocks is None:
            num_blocks = max_lanes * self.blocks_per_lane
        self.num_blocks = num_blocks
        self.allocator = BlockAllocator(num_blocks)
        self.pools = api.paged_init(num_blocks, block_size, max_lanes)
        self.layout = api.paged_layout()
        # host-owned request bookkeeping
        self.tables = np.zeros((max_lanes, self.blocks_per_lane), np.int32)
        self.pos = np.zeros((max_lanes,), np.int32)
        self.active = np.zeros((max_lanes,), bool)
        self.lane_blocks: list[list[int]] = [[] for _ in range(max_lanes)]
        self._write_fns: dict = {}

    # -- host bookkeeping ---------------------------------------------------
    def free_blocks(self) -> int:
        return self.allocator.free

    def blocks_for(self, rows: int) -> int:
        return -(-rows // self.block_size)  # ceil

    def lane_capacity(self, lane: int) -> int:
        return len(self.lane_blocks[lane]) * self.block_size

    def install_lane(self, lane: int, blocks: list[int], pos: int) -> None:
        """Point a lane at freshly allocated blocks, position ``pos``."""
        self.lane_blocks[lane] = list(blocks)
        self.tables[lane, :] = 0
        self.tables[lane, :len(blocks)] = blocks
        self.pos[lane] = pos
        self.active[lane] = True

    def grow_lane(self, lane: int, block: int) -> None:
        n = len(self.lane_blocks[lane])
        if n >= self.blocks_per_lane:
            raise MemoryError(
                f"lane {lane} already holds blocks_per_lane="
                f"{self.blocks_per_lane} blocks")
        self.lane_blocks[lane].append(block)
        self.tables[lane, n] = block

    def release_lane(self, lane: int) -> None:
        self.allocator.release(self.lane_blocks[lane])
        self.lane_blocks[lane] = []
        self.tables[lane, :] = 0
        self.pos[lane] = 0
        self.active[lane] = False

    def guard_decode_write(self) -> None:
        """Assert-guard the decode write: every active lane's next write
        position must fall inside its allocated blocks AND inside
        max_seq.  A contiguous cache's ``dynamic_update_slice`` silently
        clamps at the horizon (overwriting the last row in place); the
        paged cache refuses instead."""
        for lane in range(self.max_lanes):
            if not self.active[lane]:
                continue
            p = int(self.pos[lane])
            if p >= self.max_seq:
                raise AssertionError(
                    f"lane {lane}: decode write at pos {p} >= "
                    f"max_seq {self.max_seq} — the sequence horizon "
                    "would silently clamp; submit() should have "
                    "rejected this request")
            if p >= self.lane_capacity(lane):
                raise AssertionError(
                    f"lane {lane}: decode write at pos {p} beyond the "
                    f"lane's {len(self.lane_blocks[lane])} allocated "
                    "blocks — grow the lane (or preempt) before "
                    "stepping")

    # -- prefill write ------------------------------------------------------
    def _row_indices(self, lane: int, rows: int) -> np.ndarray:
        """Flat pool-row index for logical rows [0, rows) of ``lane``.
        Rows past the lane's allocated capacity get an out-of-range
        sentinel so the jitted scatter drops them (bucket pad rows)."""
        j = np.arange(rows)
        blk = np.zeros((rows,), np.int64)
        cap = self.lane_capacity(lane)
        valid = j < cap
        jb = j // self.block_size
        blocks = np.asarray(self.lane_blocks[lane] + [0], np.int64)
        blk[valid] = blocks[jb[valid]]
        idx = blk * self.block_size + j % self.block_size
        idx[~valid] = self.num_blocks * self.block_size  # dropped
        return idx.astype(np.int32)

    def _write_fn(self, rows: int):
        """Jitted per-(row-count) prefill scatter: one compile per
        bucket length, reused across admits."""
        if rows in self._write_fns:
            return self._write_fns[rows]
        layout = dict(self.layout)

        def write_prefill(pools, pc, idx, lane):
            new = {}
            for name, kind in layout.items():
                pool = pools[name]
                src = pc[name]
                if kind == "paged":
                    nb, bs = pool.shape[1], pool.shape[2]
                    flat = pool.reshape(
                        pool.shape[0], nb * bs, *pool.shape[3:])
                    # a pool row may flatten the cache's trailing axes
                    rows = src[:, 0].reshape(
                        src.shape[0], src.shape[2], *pool.shape[3:])
                    flat = flat.at[:, idx].set(rows, mode="drop")
                    new[name] = flat.reshape(pool.shape)
                elif kind == "lane_scalar":
                    # one scalar per lane ([max_lanes] pool, [B=1] src):
                    # e.g. the encdec streaming enc_len frame count
                    new[name] = pool.at[lane].set(src[0])
                else:  # lane-resident state, fixed size
                    new[name] = jax.lax.dynamic_update_index_in_dim(
                        pool, src[:, 0], lane, axis=1)
            return new

        # the pools are donated: the lane's rows are scattered into them
        # in place instead of into a copy of every pool
        fn = jax.jit(write_prefill, donate_argnums=(0,))
        self._write_fns[rows] = fn
        return fn

    def write_prefill(self, lane: int, prefill_cache) -> None:
        """Scatter a single-request prefill cache into ``lane``'s blocks
        (paged leaves) / lane row (lane leaves).  Dtypes must match
        exactly — a silent ``astype`` here would quietly narrow (e.g.
        fp32 state into an fp8 pool), corrupting the lane without a
        trace."""
        rows = None
        for name in self.layout:
            leaf = prefill_cache[name]
            pool = self.pools[name]
            if leaf.dtype != pool.dtype:
                raise TypeError(
                    f"prefill cache dtype {leaf.dtype} != pool dtype "
                    f"{pool.dtype} for leaf {name!r}; rebuild the "
                    "prefill cache with the engine's kv_cache_dtype "
                    "instead of relying on a silent cast")
            if self.layout[name] == "paged":
                rows = leaf.shape[2] if rows is None else rows
                if leaf.shape[2] != rows:
                    raise ValueError(
                        f"paged leaf {name!r} rows {leaf.shape[2]} != "
                        f"{rows}")
        if rows is None:  # pure lane-state family (no paged leaves)
            rows = 0
        idx = jnp.asarray(self._row_indices(lane, rows)) if rows else \
            jnp.zeros((0,), jnp.int32)
        fn = self._write_fn(rows)
        self.pools = fn(self.pools, prefill_cache, idx, lane)

    # -- decode-step device views -------------------------------------------
    def device_args(self):
        """(block_tables, pos, active) as device arrays for one step."""
        return (jnp.asarray(self.tables), jnp.asarray(self.pos),
                jnp.asarray(self.active))
