"""Dataflow-specialized tiling optimizer (paper §II-B, TPU-adapted).

SMAUG's insight: don't solve the general loop-nest problem — each accelerator
implements at most a few dataflows, so enumerate only the tiling strategies
compatible with THAT dataflow and search the narrow space exhaustively,
scoring by (a) functional-unit + scratchpad utilization and (b) the
host/HBM-side cost of materializing the tiles (layout contiguity).

TPU adaptation (DESIGN.md §2):
  scratchpad  -> VMEM budget per tile working set
  32-way MACC channel reduction (NVDLA) -> 128x128 MXU contraction tiles
  memcpy contiguity -> HBM burst contiguity (trailing-dim runs)

Outputs both abstract tile shapes (for the scheduler/simulator) and concrete
Pallas ``BlockSpec`` block shapes for the matmul kernel.
"""
from __future__ import annotations

import itertools
import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from repro.core.tensor import TensorSpec

# hardware constants (TPU v5e)
# A v5e TensorCore has 128 MiB of VMEM, but Mosaic compiles each Pallas
# kernel under a scoped limit of 16 MiB unless the call raises it:
# compiling the f32 4096^3 matmul with 512x512x2048 blocks for a
# described v5e fails with "Scoped allocation with size 19.00M and limit
# 16.00M".  Kernel tiles are sized to that limit.
VMEM_LIMIT = 16 * 1024 * 1024       # per-kernel scoped VMEM (v5e default)
MXU_DIM = 128                       # systolic array is 128x128
LANE = 128                          # last-dim register lane quantum
SUBLANE = 8                         # second-minor quantum (fp32)
HBM_LATENCY_US = 1.0                # per-transaction overhead (DMA-ish)
HBM_BW = 819e9                      # bytes/s


@dataclass(frozen=True)
class TilingChoice:
    """One evaluated tiling of a tensor."""
    strategy: str                    # e.g. "DimC", "DimHW", "DimNH"
    tile_shape: Tuple[int, ...]
    n_tiles: int
    n_memcpys: int
    contiguous_run: int              # elements per memcpy
    utilization: float               # fraction of compute-dim quantum used
    host_cost_s: float               # modeled tile-materialization time

    def __str__(self):
        return (f"{self.strategy}: tile={self.tile_shape} n={self.n_tiles} "
                f"memcpys={self.n_memcpys} run={self.contiguous_run} "
                f"util={self.utilization:.2f} host={self.host_cost_s*1e6:.1f}us")


def _host_cost(n_memcpys: int, total_bytes: int) -> float:
    """Tile-materialization cost: bandwidth term + per-memcpy overhead.
    Reproduces the Fig 6 effect: many tiny memcpys lose to few large ones."""
    return total_bytes / HBM_BW + n_memcpys * HBM_LATENCY_US * 1e-6


def enumerate_tilings(spec: TensorSpec, max_tile_elems: int,
                      reduce_dim: Optional[str] = None,
                      reduce_quantum: int = MXU_DIM) -> List[TilingChoice]:
    """All dataflow-compatible tilings of ``spec`` under the VMEM budget.

    ``reduce_dim``: the dimension the dataflow reduces over (NVDLA: channels;
    MXU matmul: the contraction dim).  Tiles keep it a multiple of
    ``reduce_quantum`` where possible (functional-unit utilization).
    """
    dims = spec.dims
    choices: List[TilingChoice] = []
    # all subsets of dims to tile (strategy DimXY... = dims being cut)
    for r in range(1, len(dims) + 1):
        for cut in itertools.combinations(range(len(dims)), r):
            strategy = "Dim" + "".join(dims[i] for i in cut)
            tile = _best_tile_for_cut(spec, cut, max_tile_elems,
                                      reduce_dim, reduce_quantum)
            if tile is None:
                continue
            n_elems_tile = math.prod(tile)
            if n_elems_tile > max_tile_elems:
                continue
            n_tiles = 1
            for full, t in zip(spec.shape, tile):
                n_tiles *= math.ceil(full / t)
            n_memcpys = spec.n_memcpys(tile)
            run = spec.contiguous_run(tile)
            util = 1.0
            if reduce_dim and reduce_dim in dims:
                rd = tile[dims.index(reduce_dim)]
                util = min(1.0, rd / reduce_quantum) if rd < reduce_quantum \
                    else (rd // reduce_quantum) * reduce_quantum / rd
            choices.append(TilingChoice(
                strategy=strategy, tile_shape=tuple(tile), n_tiles=n_tiles,
                n_memcpys=n_memcpys, contiguous_run=run, utilization=util,
                host_cost_s=_host_cost(n_memcpys, spec.nbytes)))
    return choices


def _best_tile_for_cut(spec, cut, max_tile_elems, reduce_dim, quantum):
    """Largest tile that fits when cutting exactly the dims in ``cut``."""
    tile = list(spec.shape)
    budget = max_tile_elems
    fixed = 1
    for i, d in enumerate(spec.shape):
        if i not in cut:
            fixed *= d
    if fixed > max_tile_elems:
        return None
    room = max_tile_elems // fixed
    # distribute ``room`` across cut dims: reduce dim first (functional-unit
    # quantum), then innermost-first to preserve trailing contiguity (the
    # paper's DimHW-over-DimCH effect)
    for i in sorted(cut, key=lambda i: (-(spec.dims[i] == (reduce_dim or "")),
                                        -i)):
        d = spec.shape[i]
        t = min(d, room)
        if reduce_dim and spec.dims[i] == reduce_dim and t < d:
            t = max(quantum * (t // quantum), min(d, quantum))
        t = max(1, t)
        tile[i] = t
        room = max(1, room // max(t, 1))
    if math.prod(tile) > max_tile_elems:
        # shrink the largest cut dim
        for i in sorted(cut, key=lambda i: -tile[i]):
            while math.prod(tile) > max_tile_elems and tile[i] > 1:
                tile[i] = max(1, tile[i] // 2)
    return tuple(tile)


def choose_tiling(spec: TensorSpec, max_tile_elems: int,
                  reduce_dim: Optional[str] = None,
                  w_util: float = 1.0, w_host: float = 1.0
                  ) -> TilingChoice:
    """The optimizer: exhaustively score the narrow strategy space.

    Score = utilization - normalized host cost (both effects the paper
    demonstrates; weights let case studies ablate them)."""
    cands = enumerate_tilings(spec, max_tile_elems, reduce_dim)
    if not cands:
        raise ValueError(f"no feasible tiling for {spec} within "
                         f"{max_tile_elems} elems")
    worst_host = max(c.host_cost_s for c in cands) or 1.0

    def score(c: TilingChoice) -> float:
        return w_util * c.utilization - w_host * (c.host_cost_s / worst_host)

    return max(cands, key=score)


# ---------------------------------------------------------------------------
# matmul tiling -> Pallas BlockSpec block shapes


@dataclass(frozen=True)
class MatmulTiling:
    bm: int
    bn: int
    bk: int
    vmem_bytes: int
    util_m: float
    util_n: float
    util_k: float


def matmul_vmem_bytes(bm: int, bn: int, bk: int, dtype_bytes: int) -> int:
    """VMEM the matmul kernel allocates for one block shape: the pipeline
    double-buffers the A, B and output blocks, plus the fp32 accumulator."""
    return (2 * (bm * bk + bk * bn + bm * bn) * dtype_bytes
            + bm * bn * 4)


def choose_matmul_tiling(M: int, N: int, K: int, dtype_bytes: int = 2,
                         vmem_budget: int = VMEM_LIMIT) -> MatmulTiling:
    """Block shapes for the NVDLA-adapted Pallas matmul kernel.

    The working set (:func:`matmul_vmem_bytes`) must fit ``vmem_budget``.
    Blocks are MXU-aligned (multiples of 128 where the dim allows); the K
    (reduction) dimension mirrors NVDLA's channel-block loop.
    """
    def align(x, dim):
        if dim < MXU_DIM:
            return max(SUBLANE, 1 << (dim - 1).bit_length())  # pow2 pad
        return min(x - x % MXU_DIM, dim) or MXU_DIM

    best = None
    for bm in (128, 256, 512):
        for bn in (128, 256, 512):
            for bk in (128, 256, 512, 1024, 2048):
                tbm, tbn, tbk = (min(bm, M), min(bn, N), min(bk, K))
                ws = matmul_vmem_bytes(tbm, tbn, tbk, dtype_bytes)
                if ws > vmem_budget:
                    continue
                # prefer larger K blocks (fewer partial-sum round trips),
                # then larger tiles overall
                key = (tbk, tbm * tbn, -(tbm + tbn))
                if best is None or key > best[0]:
                    best = (key, MatmulTiling(
                        bm=tbm, bn=tbn, bk=tbk, vmem_bytes=ws,
                        util_m=_mxu_util(tbm), util_n=_mxu_util(tbn),
                        util_k=_mxu_util(tbk)))
    if best is None:
        return MatmulTiling(min(128, M), min(128, N), min(128, K),
                            0, 1.0, 1.0, 1.0)
    return best[1]


def _mxu_util(t: int) -> float:
    if t >= MXU_DIM:
        return (t // MXU_DIM) * MXU_DIM / t
    return t / MXU_DIM
