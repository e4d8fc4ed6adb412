"""ELLPACK min-plus relaxation — a Pallas kernel for SSSP-Del's hot loop.

TPU adaptation (see DESIGN.md §2.7): GPU implementations scatter-min with
atomics over CSR; TPUs have no atomics and hate irregular scatters, so the
graph is re-blocked into ELLPACK — per destination row, a padded dense list
of (in-neighbor, weight).  One wave is then:

    gather (XLA) -> add -> row-min / row-argmin (kernel)

The gather of the offered distances runs in XLA before the kernel: Mosaic
lowers only 2-D gathers, so ``jnp.take`` on a whole-vector VMEM block is
refused ("Only 2D gather is supported"), and a whole [N] block would not
fit VMEM at deployment N anyway.  The kernel tiles rows in ``bm`` blocks of
the gathered offers, the neighbor ids and the weights, and writes lane-dense
``(1, R)`` outputs (a 1-D ``(bm,)`` block does not match the tiling XLA
gives a 1-D array on TPU).  This kernel compiles for TPU v5e; it is used
only when ``ell_use_kernel=True`` (DESIGN.md §2.7).

Layout notes
------------
* ``nbr_idx``/``nbr_w`` tiles are (bm, K): K is the slice's padded degree.
* padded entries carry w=+inf, idx=0 — they can never win the min.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.relax.config import resolve_interpret


def _relax_kernel(gath_ref, idx_ref, w_ref, best_ref, arg_ref):
    idx = idx_ref[...]                         # (bm, K)
    cand = gath_ref[...] + w_ref[...]          # (bm, K) offers + weights
    best = jnp.min(cand, axis=1)               # (bm,)
    # row-argmin with ties broken toward the SMALLEST NEIGHBOR ID — the same
    # rule the segment_min engine path uses, so both relaxation backends pick
    # bit-identical parents.  (min over masked ids; no iota/argmin needed.)
    is_min = cand == best[:, None]
    arg = jnp.min(jnp.where(is_min, idx, jnp.int32(2**31 - 1)), axis=1)
    best_ref[...] = best[None, :]
    arg_ref[...] = jnp.where(jnp.isfinite(best), arg, -1)[None, :]


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def ellpack_relax(dist: jax.Array, nbr_idx: jax.Array, nbr_w: jax.Array,
                  *, block_rows: int = 256, interpret: bool | None = None
                  ) -> tuple[jax.Array, jax.Array]:
    """best[i], arg[i] = min-plus reduction of row i's in-neighbors.

    Shapes: dist (N,) f32; nbr_idx (R, K) i32 (entries in [0, N)); nbr_w
    (R, K) f32 (+inf padding).  R % block_rows == 0 (host builder pads).
    ``interpret=None`` resolves to the platform default (interpret
    everywhere except TPU — kernels/relax/config.py).
    """
    interpret = resolve_interpret(interpret)
    R, K = nbr_idx.shape
    bm = min(block_rows, R)
    assert R % bm == 0, (R, bm)
    # the gather runs in XLA: Mosaic lowers only 2-D gathers, and a
    # whole-vector dist block would not fit VMEM at deployment N anyway
    gath = jnp.take(dist, nbr_idx, axis=0)
    # lane-dense (1, R) outputs: a 1-D (bm,) block does not match the
    # 1024-element tiling XLA gives a 1-D array on TPU
    tile = pl.BlockSpec((bm, K), lambda i: (i, 0))
    row = pl.BlockSpec((1, bm), lambda i: (0, i))
    best, arg = pl.pallas_call(
        _relax_kernel,
        grid=(R // bm,),
        in_specs=[tile, tile, tile],
        out_specs=[row, row],
        out_shape=[
            jax.ShapeDtypeStruct((1, R), jnp.float32),
            jax.ShapeDtypeStruct((1, R), jnp.int32),
        ],
        interpret=interpret,
    )(gath, nbr_idx, nbr_w)
    return best[0], arg[0]
