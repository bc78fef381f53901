"""Paged decode attention as a Pallas TPU kernel: one query row per slot
and head, keys and values read from the page pool where they lie.

The pool is ``(L, 2, P + 1, page, H * Dh)`` (``models.transformer.
init_kv_cache``): a page of one layer is one contiguous ``(page, H * Dh)``
slab holding every head, which fills the 128 lanes whatever the head dim.
The block table and the slots' lengths are scalar-prefetch operands; the
grid runs over the slots. A slot copies its pages in block-table order,
``block_k`` key columns (whole pages) a turn into one of two VMEM buffers
while the other is computed on, and stops after the page that holds its
last valid column: pages past the length are never read, a slot of length
0 reads none and returns zeros.

All heads of a slot are attended at once on the MXU. The query row
``(1, H * Dh)`` is spread to a block-diagonal ``(H, H * Dh)`` matrix (row h
keeps head h's lanes), so ``Qbd @ K^T`` gives the ``(H, block_k)`` scores
of every head from the lane-dense page, and ``p @ V`` gives ``(H, H * Dh)``
of which row h's own lanes are head h's output. The mathematics are
``models.transformer._paged_decode_attention``'s: bfloat16 pages, scores,
running maximum, denominator and accumulator in float32 (the products of
two bfloat16 numbers are exact there), column j valid iff
``j < lengths[b]``, the result cast to the query's type.

``interpret=None`` compiles with Mosaic on a TPU and interprets on the CPU
test mesh; any other backend raises (``context.kernel_platform``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import DEFAULT_BLOCK, _NEG_INF, _need_interpret, _round_up


def _kernel(bt_ref, len_ref, layer_ref, q_ref, pool_ref, o_ref,
            kbuf, vbuf, sems, m_ref, l_ref, acc_ref, *,
            page, chunk, head_dim, scale):
    b = pl.program_id(0)
    layer = layer_ref[0]
    length = len_ref[b]
    n_pages = (length + page - 1) // page
    n_chunks = (n_pages + chunk - 1) // chunk
    rows, hd = acc_ref.shape                     # heads padded to a tile, H*Dh
    block_k = chunk * page

    def copies(c, buf):
        """The page copies of chunk ``c`` into buffer ``buf``: one
        descriptor per page and per K/V, each guarded by the slot's page
        count, so a start and its wait see the same condition."""
        for j in range(chunk):
            idx = c * chunk + j
            for kv, dst in ((0, kbuf), (1, vbuf)):
                yield idx < n_pages, pltpu.make_async_copy(
                    pool_ref.at[layer, kv, bt_ref[b, jnp.minimum(
                        idx, bt_ref.shape[1] - 1)]],
                    dst.at[buf, pl.ds(j * page, page)],
                    sems.at[kv, buf])

    def start(c, buf):
        for ok, dma in copies(c, buf):
            pl.when(ok)(dma.start)

    def wait(c, buf):
        for ok, dma in copies(c, buf):
            pl.when(ok)(dma.wait)

    # row h of the block-diagonal query keeps the lanes of head h
    own = (jax.lax.broadcasted_iota(jnp.int32, (rows, hd), 1) // head_dim
           == jax.lax.broadcasted_iota(jnp.int32, (rows, hd), 0))
    q = q_ref[0].astype(jnp.float32)                          # (1, hd)
    qbd = jnp.where(own, jnp.broadcast_to(q, (rows, hd)),
                    0.0).astype(kbuf.dtype)

    m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(n_chunks > 0)
    def _():
        start(0, 0)

    def body(c, _):
        buf = c % 2

        @pl.when(c + 1 < n_chunks)
        def _():
            start(c + 1, 1 - buf)

        wait(c, buf)
        cols = c * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        s = jax.lax.dot_general(qbd, kbuf[buf], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(cols < length, s, _NEG_INF)             # (rows, bk)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        # a page the slot did not copy holds whatever the buffer held:
        # its columns have p == 0, and 0 * NaN must not reach the sum
        live = (c * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, 1), 0)) < length
        v = jnp.where(live, vbuf[buf].astype(jnp.float32), 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        return 0

    jax.lax.fori_loop(0, n_chunks, body, 0)
    out = jnp.where(own, acc_ref[...] / jnp.maximum(l_ref[...], 1e-30), 0.0)
    o_ref[0] = jnp.sum(out, axis=0, keepdims=True).astype(o_ref.dtype)


def paged_decode_attention(q, pool, layer, block_tables, lengths, *,
                           n_heads, block_k=None, interpret=None):
    """Attend one query row per slot over the slot's pages of ``pool``.

    q: (S, H * Dh), head h in lanes ``[h * Dh, (h + 1) * Dh)``; pool:
    (L, 2, P + 1, page, H * Dh), K at index 0 and V at 1 of axis 1; layer:
    () int32; block_tables: (S, pages per slot) int32 page ids in column
    order; lengths: (S,) int32, the slot attends columns ``< lengths[b]``
    (0: the slot reads nothing and returns zeros). ``block_k``: key
    columns per online-softmax turn, rounded down to whole pages
    (default: the flash kernels' ``DEFAULT_BLOCK``). Returns (S, H * Dh)
    in q's dtype."""
    S, hd = q.shape
    page = pool.shape[3]
    head_dim = hd // n_heads
    chunk = max(1, min(int(block_k or DEFAULT_BLOCK) // page,
                       block_tables.shape[1]))
    rows = _round_up(n_heads, 16)       # the query's rows fill whole tiles
    kernel = functools.partial(
        _kernel, page=page, chunk=chunk, head_dim=head_dim,
        scale=1.0 / (head_dim ** 0.5))
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((S, 1, hd), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S,),
            in_specs=[
                pl.BlockSpec((1, 1, hd), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, 1, hd), lambda b, *_: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, chunk * page, hd), pool.dtype),
                pltpu.VMEM((2, chunk * page, hd), pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, hd), jnp.float32),
            ]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_need_interpret(interpret),
        name="mx_paged_decode",
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32), q[:, None, :], pool)
    return out[:, 0, :]
