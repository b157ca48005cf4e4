"""Block-size autotune table for the planner Pallas kernels.

The planner kernels (``kernels/tropical_dp``, ``kernels/link_geometry``)
tile their grids by block sizes that trade VMEM residency against grid
parallelism.  The right tiles depend on the problem shape AND the
backend: on CPU the kernels run in Pallas interpret mode, where every
grid cell is executed sequentially inside the traced program — so the
fastest configuration is ONE cell covering the whole operand (the body
then vectorizes exactly like the jnp oracle); on TPU the tiles must fit
VMEM and obey Mosaic's tiling rule: the last two dimensions of every
block are multiples of (8, 128) or equal to the whole array dimension
(``divisor_leq``'s ``align`` snaps compiled blocks to it).

``lookup(kernel, ...)`` resolves a block dict for a (kernel, backend,
shape, dtype) query: an exact shape-keyed entry wins, then the backend
default, then ``{}`` (the kernel entry points fall back to whole-axis
blocks).  A block value of 0 means "the whole axis".  Entries are plain
data — measured configurations go straight into ``TABLE``.
"""
from __future__ import annotations

from typing import Dict, Optional

from repro.kernels import default_backend

#: (kernel, backend[, U, L, S, dtype]) -> block dict.  0 = whole axis.
#: The CPU rows follow from interpret mode's sequential grid.  The DP
#: kernel's TPU row was chosen by timing on one TPU v5e (below); the link
#: geometry TPU row is the smallest tile that the v5e compiler accepts at
#: the benchmark shapes (``tests/test_tpu_compile.py`` compiles both).
#: Shape-keyed rows override a backend row for one shape.
TABLE: Dict[tuple, Dict[str, int]] = {
    # CPU = interpret mode: one grid cell, fully vectorized body.
    ("tropical_dp", "cpu"): {"block_b": 0, "block_m": 0, "block_s": 0},
    ("link_geometry", "cpu"): {"block_b": 0, "block_u": 0},
    # TPU DP: 1024 scenarios (one (8, 128) vreg) x 2 slots x every state
    # per cell.  Timed on one v5e at the AlexNet cell's step (B = 8192,
    # M = 8, L = 11, S = 8, operands in HBM), 352 launches in a scan, over
    # block_b 1024..8192 x block_m 1, 2, 4, 8 x block_s 8, 4, 2: 73.4 us a
    # launch, against 74.5 us at 4 slots, 88.7 us at 1 slot, 95-105 us at
    # 8 slots or 2048 scenarios, 104-308 us for larger scenario tiles and
    # 104-220 us with the state axis split.  Link geometry tiles 8
    # scenarios (B sits on sublanes of the [B, U] planes) by whole rows.
    ("tropical_dp", "tpu"): {"block_b": 1024, "block_m": 2, "block_s": 0},
    # At ResNet-50's 52-unit chain (B = 4096, M = 8, L = 52, S = 8) the
    # row above does not fit the VMEM budget; ``_tiles`` shrinks it to 1
    # slot x 2 states, 481 us a launch on one v5e.  Under a raised 64 MiB
    # limit 2 slots x 4 states read 451 us, 2048 scenarios x 1 x 2 472 us,
    # other splits of 1-8 slots x 1-8 states 459-477 us; 2 slots x 1
    # state, which shrinking the states first gives, 829 us.
    ("link_geometry", "tpu"): {"block_b": 8, "block_u": 0},
    # GPU (Triton) runs interpret today as well — same shape as CPU.
    ("tropical_dp", "gpu"): {"block_b": 0, "block_m": 0, "block_s": 0},
    ("link_geometry", "gpu"): {"block_b": 0, "block_u": 0},
    # Backend-independent defaults for the CNN-layer kernels: these tile
    # a grid whose cells are identical on every backend (interpret mode
    # snaps blocks to whole axes via divisor_leq anyway), so one
    # "default" row per kernel is the source of truth the kernel modules
    # read their DEFAULT_BLOCK_* constants from.
    ("conv2d", "default"): {"block_m": 128, "block_n": 128,
                            "block_k": 128},
    ("decode_attention", "default"): {"block_k": 512},
    ("flash_attention", "default"): {"block_q": 128, "block_k": 128},
    ("mlstm_chunk", "default"): {"chunk": 128},
    ("moe_matmul", "default"): {"block": 128},
    ("rglru_scan", "default"): {"block_w": 128},
}


def divisor_leq(n: int, target: int, align: int = 1) -> int:
    """Largest divisor of ``n`` that is <= ``target`` and a multiple of
    ``align``; ``n`` itself when no such divisor exists.

    Pallas block shapes must tile their axis exactly (a ragged trailing
    block would read padding into the reductions), so requested block
    sizes are snapped down to a divisor of the axis length.  Compiled TPU
    kernels pass ``align`` = 8 (second-to-last block dimension) or 128
    (last): Mosaic accepts such a block only at that granularity or as
    the whole axis.
    """
    n = int(n)
    for d in range(max(1, min(int(target), n)), 0, -1):
        if n % d == 0 and d % align == 0:
            return d
    return n


def lookup(kernel: str, *, U: Optional[int] = None, L: Optional[int] = None,
           S: Optional[int] = None, dtype: str = "float32",
           backend: Optional[str] = None) -> Dict[str, int]:
    """Block dict for ``kernel`` at shape (U, L, S) / ``dtype`` on
    ``backend`` (default: the memoized process backend).  Most-specific
    entry wins; ``{}`` when the table has nothing (callers then use
    whole-axis blocks)."""
    backend = default_backend() if backend is None else backend
    for key in ((kernel, backend, U, L, S, dtype),
                (kernel, backend, U, None, None, dtype),
                (kernel, backend),
                (kernel, "default")):
        hit = TABLE.get(key)
        if hit is not None:
            return dict(hit)
    return {}


def default_blocks(kernel: str) -> Dict[str, int]:
    """The kernel's backend-independent ``(kernel, "default")`` row —
    what the kernel module's ``DEFAULT_BLOCK_*`` constants are read from.
    ``{}`` when the kernel has no default row (the planner kernels keep
    per-backend rows only)."""
    return dict(TABLE.get((kernel, "default"), {}))


__all__ = ["TABLE", "default_blocks", "divisor_leq", "lookup"]
