"""Pallas kernel: MXU segmented partial reduction (the GROUP-BY hot loop).

After sorting by key, CEM needs per-group sums of a statistics bundle
(n_t, n_c, y_t, y_c, per-covariate arm sums...). TPUs have no fast scatter;
the MXU idiom is a one-hot matmul: within a row block, partial[i, s] =
sum_j [local_seg(j) == i] * value[j, s] — a (B, B) @ (B, S) matmul that runs
on the systolic array instead of a serial scatter loop. Cross-block segment
spill is handled by a cheap jnp combine over the (nb*B, S) partials (a
segment id crosses at most nb blocks).

local_ids (= global segment id minus the block's first segment id) are
computed outside with a cumsum; the kernel is the FLOP hot spot.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(ids_ref, vals_ref, out_ref):
    ids = ids_ref[...]                 # (B,) int32, in [0, B)
    vals = vals_ref[...]               # (B, S) f32
    b = ids.shape[0]
    onehot = (ids[None, :] == jax.lax.broadcasted_iota(jnp.int32, (b, b), 0)
              ).astype(vals.dtype)     # (B, B): rows = local segment
    out_ref[...] = jnp.dot(onehot, vals,
                           preferred_element_type=jnp.float32)[None]


def segment_partials_pallas(values: jnp.ndarray, local_ids: jnp.ndarray,
                            block: int = 256, interpret: bool = True
                            ) -> jnp.ndarray:
    """values: (N, S) f32 (N % block == 0); local_ids: (N,) int32 in
    [0, block). Returns (nb, block, S) per-block partial sums."""
    n, s = values.shape
    nb = n // block
    return pl.pallas_call(
        _kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block, s), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block, s), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, block, s), jnp.float32),
        interpret=interpret,
    )(local_ids, values)


def _scatter_kernel(pos_ref, table_ref, vals_ref, out_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = table_ref[...]

    pos = pos_ref[0]                   # (1, B) int32, in [0, C)
    vals = vals_ref[...]               # (B, S) f32
    c = out_ref.shape[0]
    b = pos.shape[1]
    onehot = (pos == jax.lax.broadcasted_iota(jnp.int32, (c, b), 0)
              ).astype(vals.dtype)     # (C, B): rows = destination slot
    out_ref[...] += jnp.dot(onehot, vals,
                            preferred_element_type=jnp.float32)


def scatter_merge_pallas(table: jnp.ndarray, pos: jnp.ndarray,
                         vals: jnp.ndarray, block: int = 256,
                         interpret: bool = True) -> jnp.ndarray:
    """Online delta merge: out[pos[j], s] = table[pos[j], s] + vals[j, s].

    table: (C, S) materialized stat table; pos: (B,) destination rows
    (B % block == 0); vals: (B, S) delta stats. TPUs have no fast scatter;
    like the GROUP-BY hot loop this routes the scatter through a one-hot
    (C, B) @ (B, S) matmul per delta block, accumulating into the output
    ref across the sequential grid — duplicate positions sum, matching
    ``jnp.ndarray.at[].add`` semantics. ``input_output_aliases`` marks the
    read-modify-write on the table buffer, so on TPU the merge happens IN
    PLACE instead of materializing a second (C, S) table per call (same
    aliasing contract as :func:`scatter_merge_parts_pallas`; XLA inserts a
    copy only when the caller still needs the input table).
    """
    c, s = table.shape
    nb = pos.shape[0] // block
    # positions ride as (nb, 1, block): a block whose last two dims equal
    # the array's is legal on the chip at any nb, where a (block,) slice
    # of a longer int32 vector does not match XLA's 1-D tiling
    return pl.pallas_call(
        _scatter_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((1, 1, block), lambda i: (i, 0, 0)),
            pl.BlockSpec((c, s), lambda i: (0, 0)),
            pl.BlockSpec((block, s), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((c, s), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((c, s), jnp.float32),
        input_output_aliases={1: 0},   # table (input 1) -> merged output
        interpret=interpret,
    )(pos.reshape(nb, 1, block), table, vals)


def _scatter_parts_kernel(pos_ref, table_ref, vals_ref, out_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = table_ref[...]

    pos = pos_ref[0, 0]                # (1, B) int32, in [0, C)
    vals = vals_ref[0]                 # (B, S) f32
    c = out_ref.shape[1]
    b = pos.shape[1]
    onehot = (pos == jax.lax.broadcasted_iota(jnp.int32, (c, b), 0)
              ).astype(vals.dtype)     # (C, B): rows = destination slot
    out_ref[0] += jnp.dot(onehot, vals,
                          preferred_element_type=jnp.float32)


def scatter_merge_parts_pallas(tables: jnp.ndarray, pos: jnp.ndarray,
                               vals: jnp.ndarray, block: int = 256,
                               interpret: bool = True) -> jnp.ndarray:
    """Fused partition-local scatter merge: ONE kernel launch over a
    (n_parts, n_delta_blocks) grid instead of one :func:`scatter_merge_pallas`
    call per partition — each grid row p accumulates its partition's delta
    blocks into its own (C, S) stat table via the one-hot MXU matmul.

    tables: (P, C, S); pos: (P, B) destination slots (B % block == 0);
    vals: (P, B, S). ``input_output_aliases`` donates the table buffer, so
    on TPU the merged stats are written IN PLACE — the kernel-level analogue
    of the fused ingest program's buffer donation.
    """
    n_parts, c, s = tables.shape
    nb = pos.shape[1] // block
    return pl.pallas_call(
        _scatter_parts_kernel,
        grid=(n_parts, nb),
        in_specs=[   # positions as (P, nb, 1, block), as in scatter_merge
            pl.BlockSpec((1, 1, 1, block), lambda p, j: (p, j, 0, 0)),
            pl.BlockSpec((1, c, s), lambda p, j: (p, 0, 0)),
            pl.BlockSpec((1, block, s), lambda p, j: (p, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, c, s), lambda p, j: (p, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_parts, c, s), jnp.float32),
        input_output_aliases={1: 0},   # table buffer updates in place
        interpret=interpret,
    )(pos.reshape(n_parts, nb, 1, block), tables, vals)


def canonical_sum(x: jnp.ndarray) -> jnp.ndarray:
    """Capacity-invariant canonical sum of a zero-tail-padded stat vector.

    The device-resident query pipeline reduces per-group statistics whose
    VALID content is a key-sorted prefix and whose tail is exact zeros —
    but whose total length depends on engine layout (view capacity,
    partition count, growth history). A plain ``jnp.sum`` leaves the
    association to the compiler, which picks it per shape, per layout and
    per fusion (a vmapped spec batch reduces along a different tiled axis
    than a single query), so the same groups could reduce to different
    f32 bits. This sum fixes the association in the program itself: the
    vector is zero-padded to a power of two and folded in halves,
    ``x[:h] + x[h:]``, until one element is left — only elementwise
    adds, whose order no backend may change. It is bitwise INVARIANT to
    trailing zero padding: doubling the padded length first adds the all-
    zero upper half onto the content (exact ``+ 0.0``), then folds the
    same vector as before. Replicated / partitioned / assembled layouts,
    single and batched queries therefore all reduce to the same bits
    whenever their canonical key-sorted content matches.
    """
    n = x.shape[0]
    size = 1 << max(0, (n - 1).bit_length())
    if size != n:
        x = jnp.pad(x, (0, size - n))
    while x.shape[0] > 1:
        half = x.shape[0] // 2
        x = x[:half] + x[half:]
    return x[0]


def combine_partials(partials: jnp.ndarray, block_base: jnp.ndarray,
                     num_segments: int) -> jnp.ndarray:
    """Merge per-block partials into global per-segment sums.

    partials: (nb, B, S); block_base: (nb,) int32 = global segment id of each
    block's local segment 0. Returns (num_segments, S).
    """
    nb, b, s = partials.shape
    gid = (block_base[:, None] + jnp.arange(b, dtype=jnp.int32)[None, :]
           ).reshape(-1)
    flat = partials.reshape(nb * b, s)
    gid = jnp.clip(gid, 0, num_segments - 1)
    return jax.ops.segment_sum(flat, gid, num_segments=num_segments)
