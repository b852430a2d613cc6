"""Paged KV cache: fixed-size blocks in a preallocated device arena.

The vLLM/PagedAttention memory discipline applied to this framework's
serving plane: the K and V arenas are allocated ONCE at engine build
(``(layers, (num_blocks+1) * block_size, heads, head_dim)`` each — flat
over positions so a per-sequence *block table* maps logical position j
to arena row ``table[j // block_size] * block_size + j % block_size``),
and every sequence borrows whole blocks from a host-side free list.

Admission control is the point: a stream reserves its WORST-CASE block
count (``ceil((prompt + max_new) / block_size)``) at submit time, so
"out of KV memory" is a synchronous ``KVBudgetExceeded`` — a subclass
of the batcher's ``QueueFull``, i.e. the same HTTP 429 load-shedding
contract — never a mid-stream OOM.  Because reservation is worst-case
and release is all-at-once (finish/evict), the accounting is exact by
construction: ``allocated_total == freed_total`` whenever the engine is
drained (``tests/test_generate.py`` holds every engine it drains to that,
the admission storm included).

Block 0 is the TRASH block: it is never handed to a sequence, and the
engine points every inactive decode slot's index row at it so the fixed
-shape decode step's scatter writes land somewhere harmless.  The
``sparknet_kv_blocks_{used,total}`` gauges therefore count ALLOCATABLE
blocks only.
"""

from __future__ import annotations

import threading
from typing import List, Optional

import numpy as np

from sparknet_tpu.obs.metrics import MetricsRegistry
from sparknet_tpu.serve.batcher import QueueFull


class KVBudgetExceeded(QueueFull):
    """No free KV blocks for this stream's worst case — shed (429)."""


class KVBlockPool:
    """The device arena + host-side block allocator for one engine.

    Parameters
    ----------
    layers, heads, head_dim:
        The serving model's KV geometry (one K and one V row of
        ``(heads, head_dim)`` per layer per cached position).
    num_blocks:
        ALLOCATABLE blocks (the trash block is extra).
    block_size:
        Positions per block.
    registry:
        Optional shared MetricsRegistry for the ``sparknet_kv_*``
        series.
    """

    def __init__(
        self,
        layers: int,
        heads: int,
        head_dim: int,
        num_blocks: int = 64,
        block_size: int = 16,
        registry: Optional[MetricsRegistry] = None,
    ):
        import jax.numpy as jnp

        if num_blocks < 1 or block_size < 1:
            raise ValueError(
                f"need >= 1 block of >= 1 positions, got "
                f"{num_blocks} x {block_size}"
            )
        self.layers = int(layers)
        self.heads = int(heads)
        self.head_dim = int(head_dim)
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        # +1: block 0 is the trash block (inactive-slot scatter target)
        self.arena_rows = (self.num_blocks + 1) * self.block_size
        shape = (self.layers, self.arena_rows, self.heads, self.head_dim)
        self.k = jnp.zeros(shape, jnp.float32)
        self.v = jnp.zeros(shape, jnp.float32)

        self._lock = threading.Lock()
        self._free: List[int] = list(range(1, self.num_blocks + 1))
        # lifetime accounting (the drain-exactness pin reads these)
        self.allocated_total = 0
        self.freed_total = 0

        # like MicroBatcher: a private registry when none is shared (a
        # fleet's per-replica pools each carry their own; the standalone
        # server shares one so /metrics shows the arena)
        self.metrics = registry if registry is not None else MetricsRegistry()
        m = self.metrics
        m.gauge(
            "sparknet_kv_blocks_total",
            "allocatable KV-cache blocks in the device arena",
            fn=lambda: self.num_blocks,
        )
        m.gauge(
            "sparknet_kv_blocks_used",
            "KV-cache blocks currently reserved by live streams",
            fn=lambda: self.used(),
        )
        self.m_alloc = m.counter(
            "sparknet_kv_alloc_total",
            "KV-cache blocks reserved over the pool's lifetime",
        )
        self.m_free = m.counter(
            "sparknet_kv_free_total",
            "KV-cache blocks released over the pool's lifetime",
        )

    # ------------------------------------------------------------------
    def blocks_for(self, positions: int) -> int:
        """Blocks covering ``positions`` cached positions (ceil)."""
        return max(1, -(-int(positions) // self.block_size))

    def used(self) -> int:
        with self._lock:
            return self.num_blocks - len(self._free)

    def free_blocks(self) -> int:
        with self._lock:
            return len(self._free)

    def pressure(self) -> float:
        """Fraction of the arena currently reserved (0.0 empty, 1.0
        exhausted) — the serve plane's KV-pressure signal (the /healthz
        profile block and the request profiler's kv-bound verdict read
        it alongside the kv_reserve shed rate)."""
        if self.num_blocks <= 0:
            return 0.0
        return self.used() / self.num_blocks

    def alloc(self, n: int) -> List[int]:
        """Reserve ``n`` blocks or raise ``KVBudgetExceeded`` — all or
        nothing, so a partially-admitted stream can never strand the
        arena."""
        n = int(n)
        with self._lock:
            if n > len(self._free):
                raise KVBudgetExceeded(
                    f"KV arena out of blocks: need {n}, "
                    f"{len(self._free)}/{self.num_blocks} free"
                )
            taken, self._free = self._free[:n], self._free[n:]
            self.allocated_total += n
        self.m_alloc.inc(n)
        return taken

    def free(self, blocks: List[int]) -> None:
        if not blocks:
            return
        with self._lock:
            for b in blocks:
                if b == 0 or b in self._free:
                    raise ValueError(f"double free / trash free: block {b}")
                self._free.append(b)
            self.freed_total += len(blocks)
        self.m_free.inc(len(blocks))

    # ------------------------------------------------------------------
    def index_row(self, blocks: List[int], row_len: int) -> np.ndarray:
        """Logical position -> arena row for one sequence: position j
        lives at ``blocks[j // bs] * bs + j % bs``; positions past the
        reservation point at the trash block (they are never read —
        lengths mask them — and never written — reservation is
        worst-case)."""
        bs = self.block_size
        row = np.zeros((row_len,), np.int32)
        cover = min(row_len, len(blocks) * bs)
        j = np.arange(cover)
        row[:cover] = (
            np.asarray(blocks, np.int32)[j // bs] * bs + j % bs
        )
        return row

    @property
    def oob_row(self) -> int:
        """An out-of-bounds arena row: scatter indices set to this are
        dropped (``mode="drop"``) — how prefill skips pad positions."""
        return self.arena_rows
