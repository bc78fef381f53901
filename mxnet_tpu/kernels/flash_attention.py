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
  K blocks), both re-forming p = exp(qk - lse) blockwise on the MXU.

All matmuls use ``preferred_element_type=jnp.float32`` (MXU accumulates
fp32); inputs may be bf16. ``interpret=None`` compiles with Mosaic on a
TPU and selects interpreter mode on the CPU test mesh, so the tests
exercise the same code path; any other backend raises
(``context.kernel_platform``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..context import kernel_platform

_NEG_INF = -1e30


def _round_up(x, m):
    return -(-x // m) * m


def _need_interpret(interpret):
    if interpret is not None:
        return interpret
    return kernel_platform() == "cpu"


def _mask_scores(s, iq, jk, block_q, block_k, causal, kv_len, seq_k):
    """Apply causal and/or key-padding masks to a (block_q, block_k) score
    tile; kv_len < seq_k marks the tail keys as padding."""
    if not causal and kv_len == seq_k:
        return s
    cols = jk * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    ok = None
    if causal:
        rows = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        ok = rows >= cols
    if kv_len != seq_k:
        valid = cols < kv_len
        ok = valid if ok is None else (ok & valid)
    return jnp.where(ok, s, _NEG_INF)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                block_q, block_k, seq_k, kv_len):
    iq = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale                  # (bq, d)
    n_kb = seq_k // block_k
    if causal:
        # K blocks strictly after this Q block's last row contribute nothing
        n_kb = jnp.minimum(n_kb, ((iq + 1) * block_q + block_k - 1) // block_k)

    m0 = jnp.full((block_q, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros(q.shape, jnp.float32)

    def body(j, carry):
        m, l, acc = carry
        kb = k_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        vb = v_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = _mask_scores(s, iq, j, block_q, block_k, causal, kv_len, seq_k)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p, vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, n_kb, body, (m0, l0, acc0))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(l_safe)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   *, scale, causal, block_q, block_k, seq_k, kv_len):
    iq = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale
    do = do_ref[0].astype(jnp.float32)                        # (bq, d)
    lse = lse_ref[0]                                          # (bq, 1)
    delta = delta_ref[0]
    n_kb = seq_k // block_k
    if causal:
        n_kb = jnp.minimum(n_kb, ((iq + 1) * block_q + block_k - 1) // block_k)

    def body(j, dq):
        kb = k_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        vb = v_ref[0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = _mask_scores(s, iq, j, block_q, block_k, causal, kv_len, seq_k)
        p = jnp.exp(s - lse)                                  # (bq, bk)
        dp = jax.lax.dot_general(do, vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)                                 # (bq, bk)
        return dq + jax.lax.dot_general(ds, kb, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, n_kb, body, jnp.zeros(q.shape, jnp.float32))
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, scale, causal, block_q, block_k,
                    seq_q, seq_k, kv_len):
    jk = pl.program_id(1)
    kb = k_ref[0].astype(jnp.float32)                          # (bk, d)
    vb = v_ref[0].astype(jnp.float32)
    n_qb = seq_q // block_q
    # causal: Q blocks strictly before this K block see none of it
    start_qb = (jk * block_k) // block_q if causal else 0

    def body(i, carry):
        dk, dv = carry
        qb = q_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32) * scale
        do = do_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(i * block_q, block_q), :]
        delta = delta_ref[0, pl.ds(i * block_q, block_q), :]
        s = jax.lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = _mask_scores(s, i, jk, block_q, block_k, causal, kv_len, seq_k)
        p = jnp.exp(s - lse)                                   # (bq, bk)
        dv_new = dv + jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_new = dk + jax.lax.dot_general(ds, qb, (((0,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32)
        return dk_new, dv_new

    dk, dv = jax.lax.fori_loop(
        start_qb, n_qb, body,
        (jnp.zeros(kb.shape, jnp.float32), jnp.zeros(vb.shape, jnp.float32)))
    # qb in the loop already carries the softmax scale, so dk = ds^T @ qb
    # is fully scaled — no extra factor here (dq's kernel differs: there
    # the scale rides on s only, so dq needs the explicit * scale).
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# public API with custom VJP
# ---------------------------------------------------------------------------
def _fwd_call(q, k, v, scale, causal, block_q, block_k, interpret, kv_len):
    bh, sq, d = q.shape
    sk = k.shape[1]
    grid = (bh, sq // block_q)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, seq_k=sk,
                          kv_len=kv_len),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, sk, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            # (bh, sq, 1): Mosaic requires the last two block dims to be
            # (8k, 128k) or full-size; trailing singleton satisfies that
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
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
                    axis=-1, keepdims=True)

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
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        interpret=interpret,
        name="mx_flash_dq",
    )(q, k, v, do, lse, delta)

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
            pl.BlockSpec((1, sq, 1), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, sq, 1), lambda b, j: (b, 0, 0)),
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
    )(q, k, v, do, lse, delta)
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
    tile) once they reach it. One definition shared with the schedule
    search (tune/search.py), so candidate dedup matches the kernel
    exactly."""
    return (_effective_one(block_q, seq_q), _effective_one(block_k, seq_k))


# hand default block size (MXU-native); the schedule table can override
# per (shape, dtype, backend) when block_q/block_k are left None
DEFAULT_BLOCK = 128


def flash_attention(q, k, v, *, causal=False, sm_scale=None, block_q=None,
                    block_k=None, interpret=None):
    """Fused attention, (B, H, S, D) layout. Differentiable (custom VJP).

    Sequence lengths are padded to the block size internally (padding keys
    are masked out). ``block_q``/``block_k`` are per-call schedule
    parameters (ISSUE 10): left None, the on-disk schedule table is
    consulted at trace time for this (shape, dtype, backend) — key
    ``flash_attention`` — falling back to the MXU-native 128; an
    explicit value pins the block (bench sweeps, the tuner's own timing
    path skips the consult). ``interpret=True`` forces interpreter mode
    off-TPU.
    """
    b, h, sq, d = q.shape
    sk = k.shape[2]
    if block_q is None or block_k is None:
        from ..tune import schedule_for

        sched = schedule_for("flash_attention",
                             (b, h, sq, sk, d, int(bool(causal))),
                             str(q.dtype)) or {}
        if block_q is None:
            block_q = sched.get("block_q", DEFAULT_BLOCK)
        if block_k is None:
            block_k = sched.get("block_k", DEFAULT_BLOCK)
    scale = sm_scale if sm_scale is not None else 1.0 / (d ** 0.5)
    interp = _need_interpret(interpret)
    # Mosaic tiles refs as (8k, 128k) for fp32 / (16k, 128k) for bf16:
    # clamp to the sequence length but keep blocks tile-aligned (seq is
    # padded up to the block below, padded keys masked via kv_len).
    block_q, block_k = effective_blocks(block_q, block_k, sq, sk)

    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k
    pad_d = (-d) % 128          # lane dim: zero lanes add 0 to q·k and out
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
    if pad_d:
        qf = jnp.pad(qf, ((0, 0), (0, 0), (0, pad_d)))
        kf = jnp.pad(kf, ((0, 0), (0, 0), (0, pad_d)))
        vf = jnp.pad(vf, ((0, 0), (0, 0), (0, pad_d)))
    out = _flash(qf, kf, vf, scale, causal, block_q, block_k, interp, sk)
    if pad_q or pad_d:
        out = out[:, :sq, :d]
    return out.reshape(b, h, sq, d)
