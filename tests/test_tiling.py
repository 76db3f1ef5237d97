"""Tiling optimizer: unit + hypothesis property tests on its invariants."""
import math

import pytest
from _hyp import given, settings, st

from repro.core.tensor import TensorSpec
from repro.core.tiling import (MXU_DIM, choose_matmul_tiling, choose_tiling,
                               enumerate_tilings)

dims_st = st.tuples(st.sampled_from([1, 2, 4]),
                    st.sampled_from([4, 8, 16, 64]),
                    st.sampled_from([4, 8, 16, 64]),
                    st.sampled_from([8, 32, 128, 512]))


@given(shape=dims_st, budget=st.sampled_from([1024, 4096, 16384, 65536]))
@settings(max_examples=60, deadline=None)
def test_tiles_fit_budget_and_cover(shape, budget):
    spec = TensorSpec(shape, "NHWC", "float32")
    for c in enumerate_tilings(spec, budget, reduce_dim="C"):
        assert math.prod(c.tile_shape) <= budget
        # tiles cover the tensor
        covered = 1
        for full, t in zip(shape, c.tile_shape):
            assert 1 <= t <= full
            covered *= math.ceil(full / t)
        assert covered == c.n_tiles
        assert c.n_memcpys >= 1
        assert c.contiguous_run >= 1


@given(shape=dims_st, budget=st.sampled_from([4096, 16384]))
@settings(max_examples=40, deadline=None)
def test_chosen_is_pareto_on_host_cost(shape, budget):
    """The chosen tiling is never strictly dominated (worse util AND worse
    host cost) by another candidate."""
    spec = TensorSpec(shape, "NHWC", "float32")
    cands = enumerate_tilings(spec, budget, reduce_dim="C")
    if not cands:
        return
    best = choose_tiling(spec, budget, reduce_dim="C")
    for c in cands:
        assert not (c.utilization > best.utilization + 1e-9
                    and c.host_cost_s < best.host_cost_s - 1e-12)


def test_contiguity_beats_channel_tiling():
    """Paper Fig 6: row-wise tiling beats channel-wise for NHWC tensors."""
    spec = TensorSpec((1, 16, 16, 128), "NHWC", "float32")
    cands = {c.strategy: c for c in enumerate_tilings(spec, 16384,
                                                      reduce_dim="C")}
    assert cands["DimC"].host_cost_s > cands["DimH"].host_cost_s
    # the large-tensor case: DimHW >> cheaper than DimHC
    spec = TensorSpec((1, 64, 64, 512), "NHWC", "float32")
    cands = {c.strategy: c for c in enumerate_tilings(spec, 16384,
                                                      reduce_dim="C")}
    assert cands["DimHC"].host_cost_s > 5 * cands["DimHW"].host_cost_s
    assert cands["DimHW"].n_memcpys == 128        # paper's exact number
    assert cands["DimHW"].contiguous_run == 16384  # 16K-element memcpys


@given(m=st.sampled_from([128, 384, 1024, 4096]),
       n=st.sampled_from([128, 256, 2048]),
       k=st.sampled_from([128, 512, 5632]))
@settings(max_examples=30, deadline=None)
def test_matmul_tiling_mxu_aligned_and_fits(m, n, k):
    t = choose_matmul_tiling(m, n, k)
    assert t.bm <= m and t.bn <= n and t.bk <= k
    # double-buffered A, B and output blocks (bf16) + the fp32 accumulator
    ws = 2 * (t.bm * t.bk + t.bk * t.bn + t.bm * t.bn) * 2 + t.bm * t.bn * 4
    assert t.vmem_bytes == ws <= 16 * 1024 * 1024  # v5e scoped VMEM limit
    for b, dim in ((t.bm, m), (t.bn, n), (t.bk, k)):
        if dim >= MXU_DIM:
            assert b % MXU_DIM == 0


def test_infeasible_raises():
    spec = TensorSpec((1, 1, 1, 8), "NHWC", "float32")
    with pytest.raises(ValueError):
        choose_tiling(spec, 0, reduce_dim="C")
