"""Flash attention as a Pallas TPU kernel (forward + backward).

Reference counterpart: none — attention post-dates the reference; this is
the flagship "custom CUDA kernel → Pallas" tier (SURVEY §2.5 TPU mapping)
and the compute core of the transformer family / ring attention
(parallel/ring.py uses the same online-softmax math across devices).

Design: O(S) memory — no materialized (S, S) score matrix.

- forward: grid (B*H, S_q/block_q); K/V stay VMEM-resident per (b, h);
  fori_loop over K blocks with online softmax (running max m, denominator
  l, unnormalized accumulator) in fp32; emits out and the logsumexp rows
  needed by backward. Causal masking prunes fully-future K blocks from
  the loop bound, so causal costs ~half the FLOPs.
- backward: recomputation strategy (no (S, S) residual): one kernel
  produces dQ (grid over Q blocks), a second produces dK/dV (grid over
  K blocks), both re-forming p = exp(qk - lse) blockwise on the MXU. The
  dK/dV kernel works on the transposed tile (keys down the sublanes,
  queries along the lanes), so each of its four products is a plain
  ``a @ b`` or ``a @ b^T`` and the row statistics broadcast as they lie.

Every product takes its operands in the dtype they arrive in (bfloat16
inputs feed the MXU bfloat16; p and ds are rounded to it before their
products) and accumulates in fp32 (``preferred_element_type``); scores,
running max, denominator, accumulators, exp, lse and delta are fp32
whatever the inputs, and with fp32 inputs nothing is rounded. The softmax
scale rides on q (on k in the dK/dV kernel) where it is a power of two,
which is exact, and on the fp32 scores otherwise. Every visited block is
masked: a second, unmasked loop for the blocks wholly below the diagonal
measured slower on the chip than the mask it saves (PERF.md section 6,
PR 37). The head dim runs as it arrives: Mosaic takes a block whose last
dim is the array's whole last dim, and the v5e compiler refused none of
8, 16, 32, 40, 64, 80, 96, 128, 192, 256 in either dtype, so none is
padded. lse and delta live in HBM as (B*H, 1, S): with a trailing dim of 1
they were padded to 128 lanes there, 67 MB a layer where 0.5 MB is data.

``interpret=None`` compiles with Mosaic on a TPU and selects interpreter
mode on the CPU test mesh, so the tests exercise the same code path; any
other backend raises (``context.kernel_platform``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..context import kernel_platform

_NEG_INF = -1e30

_NT = (((1,), (1,)), ((), ()))      # a @ b^T
_NN = (((1,), (0,)), ((), ()))      # a @ b


def _round_up(x, m):
    return -(-x // m) * m


def _need_interpret(interpret):
    if interpret is not None:
        return interpret
    return kernel_platform() == "cpu"


def _dot(a, b, dims):
    # an ambient jax.default_matmul_precision("highest") asks Mosaic for
    # an fp32 contraction, which it refuses for bfloat16 operands: they
    # take the MXU's one native pass whatever the caller's context says
    precision = (None if a.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=jnp.float32)


def _fold_scale(scale):
    """(pre, post): the factor to put on an operand before the score
    product and the one left for the fp32 scores. A power of two moves
    onto the operand (exact in any float type); anything else stays on
    the scores, so no operand is rounded a second time."""
    if math.frexp(scale)[0] == 0.5:
        return scale, 1.0
    return 1.0, scale


def _scaled(x, factor):
    return x if factor == 1.0 else x * factor


def _mask_scores(s, q0, k0, q_axis, causal, kv_len, seq_k):
    """Apply causal and/or key-padding masks to a score tile whose query
    positions start at ``q0`` along ``q_axis`` and whose key positions
    start at ``k0`` along the other; kv_len < seq_k marks the tail keys as
    padding."""
    if not causal and kv_len == seq_k:
        return s
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    ok = None
    if causal:
        qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
        ok = qpos >= kpos
    if kv_len != seq_k:
        valid = kpos < kv_len
        ok = valid if ok is None else (ok & valid)
    return jnp.where(ok, s, _NEG_INF)


def _key_blocks(iq, block_q, block_k, seq_k, causal):
    """How many key blocks Q block ``iq`` visits: under the causal mask the
    blocks strictly after its last row contribute nothing."""
    n_kb = seq_k // block_k
    if causal:
        n_kb = jnp.minimum(n_kb, ((iq + 1) * block_q + block_k - 1) // block_k)
    return n_kb


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                block_q, block_k, seq_k, kv_len):
    iq = pl.program_id(1)
    pre, post = _fold_scale(scale)
    q = _scaled(q_ref[0], pre)                                # (bq, d)
    n_kb = _key_blocks(iq, block_q, block_k, seq_k, causal)

    def turn(j, carry):
        m, l, acc = carry
        kb = k_ref[0, pl.ds(j * block_k, block_k), :]
        vb = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = _scaled(_dot(q, kb, _NT), post)                   # (bq, bk)
        s = _mask_scores(s, iq * block_q, j * block_k, 0, causal, kv_len,
                         seq_k)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_new = acc * alpha + _dot(p.astype(vb.dtype), vb, _NN)
        return m_new, l_new, acc_new

    m0 = jnp.full((block_q, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros(q.shape, jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_kb, turn, (m0, l0, acc0))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    lse_ref[0] = (m + jnp.log(l_safe)).reshape(1, block_q)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   *, scale, causal, block_q, block_k, seq_k, kv_len):
    iq = pl.program_id(1)
    pre, post = _fold_scale(scale)
    q = _scaled(q_ref[0], pre)
    do = do_ref[0]                                            # (bq, d)
    lse = lse_ref[0].reshape(block_q, 1)
    delta = delta_ref[0].reshape(block_q, 1)
    n_kb = _key_blocks(iq, block_q, block_k, seq_k, causal)

    def turn(j, dq):
        kb = k_ref[0, pl.ds(j * block_k, block_k), :]
        vb = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = _scaled(_dot(q, kb, _NT), post)
        s = _mask_scores(s, iq * block_q, j * block_k, 0, causal, kv_len,
                         seq_k)
        p = jnp.exp(s - lse)                                  # (bq, bk)
        ds = p * (_dot(do, vb, _NT) - delta)
        return dq + _dot(ds.astype(kb.dtype), kb, _NN)

    dq = jax.lax.fori_loop(0, n_kb, turn, jnp.zeros(q.shape, jnp.float32))
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, scale, causal, block_q, block_k,
                    seq_q, seq_k, kv_len):
    jk = pl.program_id(1)
    pre, post = _fold_scale(scale)
    vb = v_ref[0]                                             # (bk, d)
    kb = _scaled(k_ref[0], pre)
    # causal: Q blocks strictly before this K block see none of it
    start_qb = (jk * block_k) // block_q if causal else 0

    def turn(i, carry):
        dk, dv = carry
        qb = q_ref[0, pl.ds(i * block_q, block_q), :]
        do = do_ref[0, pl.ds(i * block_q, block_q), :]
        st = _scaled(_dot(kb, qb, _NT), post)                 # (bk, bq)
        st = _mask_scores(st, i * block_q, jk * block_k, 1, causal, kv_len,
                          seq_k)
        pt = jnp.exp(st - lse_ref[0, i])                      # rows (1, bq)
        dv_new = dv + _dot(pt.astype(do.dtype), do, _NN)
        dst = pt * (_dot(vb, do, _NT) - delta_ref[0, i])
        dk_new = dk + _dot(dst.astype(qb.dtype), qb, _NN)
        return dk_new, dv_new

    zeros = jnp.zeros(vb.shape, jnp.float32)
    dk, dv = jax.lax.fori_loop(start_qb, seq_q // block_q, turn,
                               (zeros, zeros))
    dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# public API with custom VJP
# ---------------------------------------------------------------------------
def _fwd_call(q, k, v, scale, causal, block_q, block_k, interpret, kv_len):
    bh, sq, d = q.shape
    sk = k.shape[1]
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, seq_k=sk,
                          kv_len=kv_len),
        grid=(bh, sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, sk, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32),
        ],
        interpret=interpret,
        name="mx_flash_fwd",
    )(q, k, v)
    return out, lse


def _bwd_call(q, k, v, do, out, lse, scale, causal, block_q, block_k,
              interpret, kv_len):
    bh, sq, d = q.shape
    sk = k.shape[1]
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[:, None, :]                      # (bh, 1, sq)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, seq_k=sk,
                          kv_len=kv_len),
        grid=(bh, sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, sk, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
            pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        interpret=interpret,
        name="mx_flash_dq",
    )(q, k, v, do, lse, delta)

    n_qb = sq // block_q
    # one (1, block_q) row a Q block, picked by the loop index on an
    # untiled leading dim
    rows = pl.BlockSpec((1, n_qb, 1, block_q), lambda b, j: (b, 0, 0, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, seq_q=sq,
                          seq_k=sk, kv_len=kv_len),
        grid=(bh, sk // block_k),
        in_specs=[
            pl.BlockSpec((1, sq, d), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, sq, d), lambda b, j: (b, 0, 0)),
            rows, rows,
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ],
        interpret=interpret,
        name="mx_flash_dkv",
    )(q, k, v, do, lse.reshape(bh, n_qb, 1, block_q),
      delta.reshape(bh, n_qb, 1, block_q))
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, scale, causal, block_q, block_k, interpret, kv_len):
    out, _ = _fwd_call(q, k, v, scale, causal, block_q, block_k, interpret,
                       kv_len)
    return out


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret, kv_len):
    out, lse = _fwd_call(q, k, v, scale, causal, block_q, block_k, interpret,
                         kv_len)
    return out, (q, k, v, out, lse)


def _flash_bwd(scale, causal, block_q, block_k, interpret, kv_len, res, do):
    q, k, v, out, lse = res
    dq, dk, dv = _bwd_call(q, k, v, do, out, lse, scale, causal, block_q,
                           block_k, interpret, kv_len)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def _effective_one(block, seq):
    if block is None:
        return None
    seq = max(int(seq), 1)
    if block >= seq:
        # full-size block: Mosaic accepts the whole dimension as one
        # tile, so clamp EXACTLY to the sequence — rounding up past it
        # would only pad. The decode shape (seq_q == 1) depends on
        # this: block_q must clamp to 1, not round up to a 16-row tile
        # the single query would rattle around in (ISSUE 12).
        return seq
    return _round_up(block, 16)


def effective_blocks(block_q, block_k, seq_q, seq_k):
    """The block sizes a (block_q, block_k) request actually runs with:
    rounded up to the 16-row Mosaic tile while smaller than the
    sequence, clamped to exactly the sequence length (a legal full-size
    tile) once they reach it; None (no request) stays None. One
    definition shared with the schedule search (tune/search.py), so
    candidate dedup matches the kernel exactly."""
    return (_effective_one(block_q, seq_q), _effective_one(block_k, seq_k))


# the MXU-native tile: the granule a long sequence is padded to, and the
# paged decode kernel's key chunk (kernels/paged_decode.py)
DEFAULT_BLOCK = 128

# the largest block a sequence axis is cut into when none is requested.
# One chip sweep at B 2, H 32, S 2048, D 64, bfloat16, causal (v5e;
# tools/flash_probe.py --sweep, PERF.md section 6, PR 37): 512 x 512 is
# the fastest of 128-2048 on each axis for each of the three kernels
# (forward 1.03 ms, 128 x 128: 3.41); smaller blocks pay a fixed cost a
# loop turn more often, larger ones compute more of the masked half of
# the diagonal. It fits the default scoped VMEM for head dims up to 256
# in either dtype; what runs out first is K and V held whole.
_BLOCK_CEILING = 512


def _derived_block(seq):
    """The block for a sequence axis nobody pinned: the whole sequence up
    to the ceiling, else the largest 128 * 2**n under the ceiling that
    pads no further than the 128 granule does."""
    if seq <= _BLOCK_CEILING:
        return seq
    padded = _round_up(seq, DEFAULT_BLOCK)
    block = DEFAULT_BLOCK
    while block * 2 <= _BLOCK_CEILING and padded % (block * 2) == 0:
        block *= 2
    return block


def flash_attention(q, k, v, *, causal=False, sm_scale=None, block_q=None,
                    block_k=None, interpret=None):
    """Fused attention, (B, H, S, D) layout. Differentiable (custom VJP).

    Sequence lengths are padded to the block size internally (padding keys
    are masked out); the head dim runs as it is. ``block_q``/``block_k``
    are per-call schedule parameters (ISSUE 10): left None, the on-disk
    schedule table is consulted at trace time for this (shape, dtype,
    backend) — key ``flash_attention`` — falling back to a block derived
    from the sequence (``_derived_block``: the sequence itself up to 512,
    the measured optimum beyond); an explicit value pins the block (bench
    sweeps, the tuner's own timing path skips the consult).
    ``interpret=True`` forces interpreter mode off-TPU.
    """
    b, h, sq, d = q.shape
    sk = k.shape[2]
    if block_q is None or block_k is None:
        from ..tune import schedule_for

        sched = schedule_for("flash_attention",
                             (b, h, sq, sk, d, int(bool(causal))),
                             str(q.dtype)) or {}
        if block_q is None:
            block_q = sched.get("block_q")
        if block_k is None:
            block_k = sched.get("block_k")
    scale = sm_scale if sm_scale is not None else 1.0 / (d ** 0.5)
    interp = _need_interpret(interpret)
    # Mosaic tiles refs as (8k, 128k) for fp32 / (16k, 128k) for bf16:
    # blocks stay tile-aligned or span the whole (padded) sequence, and
    # padded keys are masked via kv_len
    block_q, block_k = effective_blocks(block_q, block_k, sq, sk)
    block_q = block_q or _derived_block(sq)
    block_k = block_k or _derived_block(sk)

    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * h, sk, d)
    vf = v.reshape(b * h, sk, d)
    if pad_q:
        qf = jnp.pad(qf, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        # padded key columns are masked to -inf inside the kernels
        # (kv_len carries the true length), so zero-padding is safe
        kf = jnp.pad(kf, ((0, 0), (0, pad_k), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad_k), (0, 0)))
    out = _flash(qf, kf, vf, scale, causal, block_q, block_k, interp, sk)
    if pad_q:
        out = out[:, :sq]
    return out.reshape(b, h, sq, d)
