"""The expert layer's combine as a Pallas TPU kernel: the float32 rows of a
pass of the row buffer added into the tokens they were gathered from.

``out[t] = carry[t] + sum of rows[r]`` over the pass's routed rows r whose
token is t (zeros in place of ``carry`` on a pass that has none). XLA's
``y.at[kept].add(rows, mode="drop")`` does the same as a scatter-add: it
sorts the updates by token itself, then makes one read-modify-write of a
``[T, h]`` row per update, and on the v5e that costs 107-183 ns a row, a
quarter of the HBM rate (PERF.md section 6, PR 33).

Here the grid walks blocks of tokens, each block's float32 accumulator
resident in VMEM and written once. Lists built from the rows' tokens
(``token_order``, int32 work; in SMEM by scalar prefetch) say which rows a
block takes, in ascending row index: the array in HBM is tiled (8, 128), so
a copy moves the 8-row group that holds a row, and a block's rows come in
one run per held expert (each expert's rows are sorted by token), so most
groups copied are full. ``IN_FLIGHT`` group copies run ahead into a ring of
VMEM slots, across block boundaries; each listed row is added from its slot
into its token's row of the accumulator. A token's rows are added in
ascending row index, the order in which the scatter adds them, so the sums
are the scatter's bit for bit. Rows that are not listed are never added:
the rows past the groups, which Mosaic's grouped product leaves holding
whatever was in memory, cannot reach a token (the lists do what
``mode="drop"`` does).

The kernel takes float32 rows whose width is whole 128-lane blocks and a
token count in whole rows of 8 (``plan``); other shapes keep the
scatter-add. Off the TPU it runs in interpret mode, as the flash kernels do.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

IN_FLIGHT = 16  # 8-row groups in flight (8 read 3-5% slower: PERF.md 6)
BLOCK_BYTES = 8 << 20  # a token block's float32 accumulator in VMEM
GROUP = 8  # rows of a copy: the HBM array is tiled (8, 128), a slice of it
#            starts and ends on whole tiles


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def plan(tokens, lanes):
    """(tokens a grid step takes, None), or (None, why the kernel cannot
    take float32 rows of ``lanes`` lanes into ``tokens`` tokens)."""
    if lanes % 128:
        return None, "lanes_not_blocks_of_128"
    if tokens % 8:
        return None, "tokens_not_rows_of_8"
    block = 8
    while (block * 2 * lanes * 4 <= BLOCK_BYTES
           and tokens % (block * 2) == 0):
        block *= 2
    return block, None


def token_order(kept, tokens, block):
    """What the kernel walks, from ``kept`` (each row's token; a row sent to
    ``tokens`` is routed nowhere), int32 work only. The entries: the routed
    rows by block of ``block`` tokens and, inside a block, by row (so a
    token's rows come in ascending row index), each as token x 8 + the row's
    place in its group of 8; the groups: the distinct 8-row groups of each
    block's entries in that order, each as its first row and its first
    entry; and each block's first group, the last one's end at the close."""
    rows = kept.shape[0]
    place = jnp.arange(rows, dtype=jnp.int32)
    blk, order, tok = jax.lax.sort(
        (kept // np.int32(block), place, kept), num_keys=2)
    group = order // np.int32(GROUP)
    new = jnp.concatenate([jnp.ones((1,), bool),
                           (group[1:] != group[:-1]) | (blk[1:] != blk[:-1])])
    before = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(new, dtype=jnp.int32)])
    # the groups in order, by a sort: a scatter of int32s costs more
    group_entry, group_row = jax.lax.sort(
        (jnp.where(new, place, np.int32(rows)), group * np.int32(GROUP)),
        num_keys=1)
    firsts = jnp.searchsorted(
        blk, jnp.arange(tokens // block + 1, dtype=jnp.int32), side="left",
        method="compare_all")
    return (tok * np.int32(GROUP) + order % np.int32(GROUP), group_row,
            jnp.concatenate([group_entry, jnp.full((1,), rows, jnp.int32)]),
            before[firsts])


def _kernel(entry_ref, group_row_ref, group_entry_ref, firsts_ref, rows_hbm,
            *refs, block, carried):
    if carried:
        carry_ref, out_ref, ring, sems = refs
    else:
        out_ref, ring, sems = refs
    i = pl.program_id(0)
    groups = firsts_ref[pl.num_programs(0)]

    def slot(g):
        return jax.lax.rem(g, np.int32(IN_FLIGHT))

    def copy(g):
        first = pl.multiple_of(group_row_ref[g], GROUP)
        return pltpu.make_async_copy(rows_hbm.at[pl.ds(first, GROUP)],
                                     ring.at[slot(g)], sems.at[slot(g)])

    @pl.when(i == 0)
    def _():
        for g in range(IN_FLIGHT - 1):
            @pl.when(g < groups)
            def _():
                copy(np.int32(g)).start()

    if carried:
        out_ref[...] = carry_ref[...]
    else:
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)
    base = i * block

    def add(j, g):
        t = jax.lax.div(entry_ref[j], np.int32(GROUP)) - base
        row = ring[slot(g), pl.ds(jax.lax.rem(entry_ref[j], np.int32(GROUP)),
                                  1), :]
        out_ref[pl.ds(t, 1), :] = out_ref[pl.ds(t, 1), :] + row
        return g

    def take(g, carry):
        copy(g).wait()
        # the slot the last group was in is free: refill it
        @pl.when(g + IN_FLIGHT - 1 < groups)
        def _():
            copy(g + IN_FLIGHT - 1).start()

        jax.lax.fori_loop(group_entry_ref[g], group_entry_ref[g + 1], add, g)
        return carry

    jax.lax.fori_loop(firsts_ref[i], firsts_ref[i + 1], take, np.int32(0))


@functools.partial(jax.jit, static_argnames=("block",))
def moe_combine(rows, entry, group_row, group_entry, firsts, carry=None, *,
                block):
    """``carry`` (or zeros) [T, h] float32 plus each listed row of ``rows``
    [R, h] float32 added into its token: the lists from
    ``token_order(kept, T, block)``, ``block`` from ``plan``."""
    tokens, lanes = block * (firsts.shape[0] - 1), rows.shape[1]
    spec = pl.BlockSpec((block, lanes), lambda i, *_: (i, np.int32(0)))
    block_bytes = block * lanes * 4
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(tokens // block,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)]
        + [spec] * (carry is not None),
        out_specs=spec,
        scratch_shapes=[pltpu.VMEM((IN_FLIGHT, GROUP, lanes), jnp.float32),
                        pltpu.SemaphoreType.DMA((IN_FLIGHT,))])
    return pl.pallas_call(
        functools.partial(_kernel, block=block, carried=carry is not None),
        name="moe_combine",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((tokens, lanes), jnp.float32),
        # a later pass adds into the carried sum where it lies
        input_output_aliases={5: 0} if carry is not None else {},
        # the copies run ahead across blocks: the grid is walked in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=2 * block_bytes * (1 + (carry is not None))
            + IN_FLIGHT * GROUP * lanes * 4 + (4 << 20)),
        interpret=_interpret(),
    )(entry, group_row, group_entry, firsts, rows,
      *([carry] if carry is not None else []))
