"""Selective-scan (Mamba1) kernel (Pallas, TPU target).

The lax.scan baseline round-trips the (d_inner, N) state through HBM every
timestep — the memory-bound term the falcon-mamba §Perf iteration attacks.
This kernel keeps the state in VMEM across a whole sequence chunk:

  grid = (B, d_inner/bd, S/chunk)    chunk innermost, sequential
  state scratch (N, bd) persists across chunk steps (VMEM-resident)
  inside a chunk: fori_loop over SLAB-row slabs of timesteps, each slab
  unrolled statically (VREG/VMEM only)

B/C are shared across channels (per Mamba1), A is (d, N) channel-specific.

Layout.  The state is held as (N, bd): channels on the 128-wide lanes,
the small state dim on sublanes, so one (N, bd) f32 array fills whole
vregs.  x, dt and y are read and written as ``SLAB``-row slabs at offsets
the compiler can prove are multiples of ``SLAB``: Mosaic refuses a
dynamic single-row access into the sublane dimension.  B and C enter
transposed, (b, N, S), so the timestep sits on lanes and column t is
picked by a masked lane reduction, with no dynamic lane index.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# timesteps per aligned slab: the bf16 sublane tile is 16 rows (f32: 8),
# so 16-row slabs are tile-aligned for both dtypes
SLAB = 16


def _scan_kernel(x_ref, dt_ref, bt_ref, ct_ref, at_ref, d_ref, o_ref, h_ref,
                 *, chunk: int):
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    at = at_ref[...]                                  # (N, bd) fp32
    dvec = d_ref[...]                                 # (1, bd) fp32
    bt = bt_ref[0].astype(jnp.float32)                # (N, chunk)
    ct = ct_ref[0].astype(jnp.float32)                # (N, chunk)
    lane_t = jax.lax.broadcasted_iota(jnp.int32, bt.shape, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (SLAB, dvec.shape[1]), 0)

    def slab(j, h):
        base = pl.multiple_of(j * SLAB, SLAB)
        xs = x_ref[0, pl.ds(base, SLAB), :].astype(jnp.float32)   # (SLAB, bd)
        dts = dt_ref[0, pl.ds(base, SLAB), :].astype(jnp.float32)
        ys = jnp.zeros_like(xs)
        for r in range(SLAB):                         # static unroll
            x_t = xs[r:r + 1]                         # (1, bd)
            dt_t = dts[r:r + 1]
            sel = lane_t == base + r
            b_t = jnp.sum(jnp.where(sel, bt, 0.0), axis=1, keepdims=True)
            c_t = jnp.sum(jnp.where(sel, ct, 0.0), axis=1, keepdims=True)
            h = jnp.exp(dt_t * at) * h + b_t * (dt_t * x_t)        # (N, bd)
            y = jnp.sum(h * c_t, axis=0, keepdims=True) + dvec * x_t
            ys = jnp.where(row == r, y, ys)
        o_ref[0, pl.ds(base, SLAB), :] = ys.astype(o_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, chunk // SLAB, slab, h_ref[...])


@functools.partial(jax.jit, static_argnames=("bd", "chunk", "interpret"))
def mamba_scan(x, dt, B, C, A, D, *, bd: int = 0, chunk: int = 0,
               interpret: bool = False):
    """x, dt: (b, S, d); B, C: (b, S, N); A: (d, N) fp32; D: (d,) fp32.
    Returns y: (b, S, d).  ``chunk`` must be a multiple of ``SLAB``."""
    bsz, S, d = x.shape
    N = B.shape[-1]
    bd = min(bd or min(d, 512), d)
    chunk = min(chunk or min(S, 128), S)
    assert d % bd == 0 and S % chunk == 0 and chunk % SLAB == 0, \
        (d, bd, S, chunk, SLAB)
    grid = (bsz, d // bd, S // chunk)
    return pl.pallas_call(
        functools.partial(_scan_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, bd), lambda b, i, s: (b, s, i)),  # x
            pl.BlockSpec((1, chunk, bd), lambda b, i, s: (b, s, i)),  # dt
            pl.BlockSpec((1, N, chunk), lambda b, i, s: (b, 0, s)),   # B^T
            pl.BlockSpec((1, N, chunk), lambda b, i, s: (b, 0, s)),   # C^T
            pl.BlockSpec((N, bd), lambda b, i, s: (0, i)),            # A^T
            pl.BlockSpec((1, bd), lambda b, i, s: (0, i)),            # D
        ],
        out_specs=pl.BlockSpec((1, chunk, bd), lambda b, i, s: (b, s, i)),
        out_shape=jax.ShapeDtypeStruct((bsz, S, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, bd), jnp.float32)],
        interpret=interpret,
    )(x, dt, B.transpose(0, 2, 1), C.transpose(0, 2, 1), A.T,
      D.reshape(1, d))
