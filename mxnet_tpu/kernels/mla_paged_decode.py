"""Absorbed latent (MLA) decode attention as a Pallas TPU kernel: every head
of a slot attends the slot's cached latent rows where they lie in the pool.

In the absorbed form all heads share one key, the cached row
``[c_kv | k_rope | 0]`` of ``W`` lanes (``models.mla_moe._latent_width``),
and its first ``d_value`` lanes (``c_kv``) are the value too.  The query of
head h is ``[q_nope[h] W_kb[h]^T | q_rope[h] | 0]``, as wide as a row, so
one product of the ``(H, W)`` queries with a block of rows gives the scores
of every head, and ``P`` times the same rows' head gives ``P c_kv``.

The pool is ``(P + 1, page, W)``, one sublayer's own.  The block table and
the slots' lengths are scalar-prefetch operands; the grid runs over the
slots.  A slot copies its pages in block-table order, ``block_k`` rows (whole
pages) a turn into one of two VMEM buffers while the other is computed on,
and stops after the page that holds its last valid row: pages past the
length are never read, a slot of length 0 reads none and returns zeros.
Rows, ``P`` and the queries are in the pool's type; scores, running maximum,
denominator and accumulator in float32 (online softmax); row j is valid iff
``j < lengths[b]``.

``interpret=None`` compiles with Mosaic on a TPU and interprets on the CPU
test mesh; any other backend raises (``context.kernel_platform``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import DEFAULT_BLOCK, _NEG_INF, _dot, _need_interpret


def _kernel(bt_ref, len_ref, q_ref, pool_ref, o_ref, buf, sems, m_ref, l_ref,
            acc_ref, *, page, chunk, d_value, scale):
    b = pl.program_id(0)
    length = len_ref[b]
    n_pages = (length + page - 1) // page
    n_chunks = (n_pages + chunk - 1) // chunk
    block_k = chunk * page

    def copies(c, slot):
        """The page copies of chunk ``c`` into buffer ``slot``: one
        descriptor a page, each guarded by the slot's page count, so a
        start and its wait see the same condition."""
        for j in range(chunk):
            idx = c * chunk + j
            yield idx < n_pages, pltpu.make_async_copy(
                pool_ref.at[bt_ref[b, jnp.minimum(idx, bt_ref.shape[1] - 1)]],
                buf.at[slot, pl.ds(j * page, page)], sems.at[slot])

    def start(c, slot):
        for ok, dma in copies(c, slot):
            pl.when(ok)(dma.start)

    def wait(c, slot):
        for ok, dma in copies(c, slot):
            pl.when(ok)(dma.wait)

    # a page a slot does not copy leaves what the buffer held: rows of an
    # earlier turn (finite, and their P is 0), or, before the first copy,
    # whatever the memory held, and 0 * NaN must not reach the sum
    @pl.when(b == 0)
    def _():
        buf[...] = jnp.zeros(buf.shape, buf.dtype)

    m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(n_chunks > 0)
    def _():
        start(0, 0)

    q = q_ref[0]                                               # (H, W)

    def body(c, _):
        slot = c % 2

        @pl.when(c + 1 < n_chunks)
        def _():
            start(c + 1, 1 - slot)

        wait(c, slot)
        rows = buf[slot]                                       # (bk, W)
        cols = c * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        s = _dot(q, rows, (((1,), (1,)), ((), ()))) * scale    # (H, bk)
        s = jnp.where(cols < length, s, _NEG_INF)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + _dot(
            p.astype(rows.dtype), rows[:, :d_value], (((1,), (0,)), ((), ())))
        m_ref[...] = m_new
        return 0

    jax.lax.fori_loop(0, n_chunks, body, 0)
    o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def mla_paged_decode_attention(q, pool, block_tables, lengths, *, d_value, scale,
                               block_k=None, interpret=None):
    """Attend the ``H`` absorbed queries of every slot over the slot's pages.

    q: (S, H, W) in the pool's type; pool: (P + 1, page, W) rows
    ``[c_kv | k_rope | 0]``; block_tables: (S, pages per slot) int32 page ids
    in row order; lengths: (S,) int32, a slot attends rows ``< lengths[b]``
    (0: it reads nothing and returns zeros).  ``d_value``: leading lanes of a
    row that are its value (a multiple of 128); ``scale`` multiplies the
    scores; ``block_k``: rows per online-softmax turn, rounded down to whole
    pages (default: the flash kernels' ``DEFAULT_BLOCK``).  Returns
    (S, H, d_value) in q's type: softmax(scale q . rows) rows[:, :d_value]."""
    S, H, W = q.shape
    page = pool.shape[1]
    chunk = max(1, min(int(block_k or DEFAULT_BLOCK) // page, block_tables.shape[1]))
    kernel = functools.partial(_kernel, page=page, chunk=chunk, d_value=d_value,
                               scale=float(scale))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((S, H, d_value), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S,),
            in_specs=[
                pl.BlockSpec((1, H, W), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, H, d_value), lambda b, *_: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, chunk * page, W), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, d_value), jnp.float32),
            ]),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=_need_interpret(interpret),
        name="mx_mla_paged_decode",
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32), q, pool)
