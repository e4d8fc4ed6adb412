"""Embedding-bag — Pallas TPU kernel for the sparse-table lookup hot path.

RecSys tables are huge (1e6–1e9 rows) and live in HBM; the bag indices are
small.  TPU-native plan (vs. GPU's warp-per-bag gather):

  * the table stays in HBM (``memory_space=pl.ANY``) — rows are DMA'd on
    demand with dynamic slices;
  * the grid tiles bags in ``bb`` blocks; each block's (bb, L) indices sit in
    VMEM and a fori_loop walks bag slots, issuing a (bb?, D)-row dynamic load
    per (bag, slot) and accumulating in a VMEM f32 scratch;
  * D is padded to lane width (128) by the caller (ops.py).

This mirrors the classic TPU embedding pattern (scalar-prefetched row DMA +
vector accumulate).  On-CPU validation uses interpret=True.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _make_kernel(bb: int, L: int, agg: str, out_dtype):
    def kernel(idx_ref, table_ref, out_ref):
        def bag_body(b, acc):
            def slot_body(l, ac):
                i = idx_ref[b, l]
                valid = i >= 0
                safe = jnp.maximum(i, 0)
                row = table_ref[pl.ds(safe, 1), :]
                row = row.astype(jnp.float32)
                return ac.at[b].add(jnp.where(valid, row[0], 0.0))
            return jax.lax.fori_loop(0, L, slot_body, acc)

        acc0 = jnp.zeros(out_ref.shape, jnp.float32)
        acc = jax.lax.fori_loop(0, bb, bag_body, acc0)
        if agg == "mean":
            cnt = jnp.maximum(
                jnp.sum((idx_ref[...] >= 0).astype(jnp.float32), axis=1,
                        keepdims=True), 1.0)
            acc = acc / cnt
        out_ref[...] = acc.astype(out_dtype)
    return kernel


@functools.partial(jax.jit, static_argnames=("agg", "block_bags", "interpret"))
def embedding_bag(table: jax.Array, idx: jax.Array, *, agg: str = "sum",
                  block_bags: int = 8, interpret: bool = False) -> jax.Array:
    """table (V, D) f32/bf16; idx (B, L) i32 (-1 = pad) -> (B, D)."""
    V, D = table.shape
    B, L = idx.shape
    bb = min(block_bags, B)
    assert B % bb == 0, (B, bb)
    grid = (B // bb,)
    return pl.pallas_call(
        _make_kernel(bb, L, agg, table.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, L), lambda i: (i, 0)),          # indices (VMEM)
            pl.BlockSpec(memory_space=pl.ANY),                # table in HBM
        ],
        out_specs=pl.BlockSpec((bb, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, D), table.dtype),
        interpret=interpret,
    )(idx, table)
