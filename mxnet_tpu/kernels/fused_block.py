"""Fused ResNet bottleneck block as Pallas TPU kernels (fwd + bwd).

Reference counterpart: the conv/BN/ReLU chains built by
``example/image-classification/symbols/resnet.py`` residual_unit — on the
reference stack each op is a separate cuDNN/CUDA kernel and the
activations round-trip device memory between them. The round-5
compiler estimate (ROADMAP S1) says the TPU port is HBM-bandwidth-bound
the same way:
XLA materializes every BN input/output, so a ResNet-50 train step moves
~78 GB/step where ~48 GB is the structural minimum.

This module removes the extra passes with a small library of Pallas
convolution kernels in NHWC whose contract is:

- **prologue**: BatchNorm-apply + ReLU folded into the conv's *input
  read* — the normalized activation lives only in VMEM, never in HBM.
- **epilogue**: per-channel sum / sum-of-squares of the conv's *output
  write* — the next BatchNorm's statistics cost no extra pass.
- backward mirrors it: the BN/ReLU backward elementwise math rides the
  wgrad/dgrad kernels' operand reads (``bnbwd`` prologue), and dgrad
  accumulates the (dbeta, dgamma) reductions as it writes.

Every intermediate activation therefore crosses HBM exactly once, raw
(the conv output), which is the minimum any schedule with true training
BN semantics can do.

MXU blocking (round 6): the round-4/5 kernels tiled the grid
``(image, row-tile)`` so every MXU call saw a ``(th*W_out, Ci)`` row
block — at ResNet-50 shapes that is 196-784 rows against Ci,Co as
small as 64, and the round-5 on-chip measurement (ROADMAP S2) showed
the resulting MXU underutilization costs 2.5x more than the HBM
traffic the fusion saves. The grid is now
``(channel-block, batch-block, row-tile)`` with **the batch folded
into the matmul row dimension**: each kernel instance holds ``nb``
images' row tiles and issues matmuls of shape
``(nb*th*W_out, Ci) @ (Ci, co_block)``, with ``nb`` chosen per shape
(``_batch_fold``) so every MXU call meets the
``MXU_WORK_FLOOR = 256*256*256`` multiply-accumulate floor, and output
channels blocked to 256 lanes (``_chan_block``). Grid dimensions carry
``dimension_semantics`` — channel blocks are ``parallel``; the
batch/row dims that accumulate into a revisited output (BN stats, dw)
are ``arbitrary``. ``set_row_tile`` / ``MXNET_TPU_FUSED_ROW_TILE``
expose the row-tile size as a knob; ``mxu_plan`` reports the matmul
tile a given conv shape gets, so tests and benchmarks can assert the
work floor at real shapes.

Layout: NHWC with channels on the TPU lane dimension; weights HWIO.
1x1 convs are per-pixel matmuls; 3x3 convs are 9 shifted matmuls over a
spatially tiled block with 1-row halos (halo rows enter as extra
1-row BlockSpec operands, so no manual DMA is needed). Stride-2
backward uses zero-stuffed input tiles (transposed conv), built with
interleave/concat only — no pad/scatter primitives, so the kernels
lower on Mosaic and run identically under ``interpret``.

``interpret=None`` auto-selects interpreter mode off-TPU so the CPU
test mesh runs the same code path (same convention as
flash_attention.py).
"""
from __future__ import annotations

import contextlib
import functools
import threading

import jax
import jax.numpy as jnp
import numpy as _np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from .. import config as _config
from ..context import kernel_platform
from ..util import shard_map as _shard_map

# One MXU call must see at least this many multiply-accumulates
# (M*K*N >= 256^3): below it the systolic array spends its cycles on
# fill/drain instead of work — the measured round-5 failure mode.
MXU_WORK_FLOOR = 256 * 256 * 256

# Per-array per-block VMEM element budget for the batch-fold chooser
# (~2 MB bf16 / 4 MB f32 per array; Pallas double-buffers inputs, so
# the practical ceiling across all of a kernel's blocks stays well
# under the 16 MB scoped-vmem limit).
_VMEM_BLOCK_ELEMS = 1 << 20

# Row-tile knob: rows of conv output per grid tile (per image). None ->
# MXNET_TPU_FUSED_ROW_TILE env var -> 16. Settable at runtime with
# set_row_tile() for sweeps (tools/bench_kernel.py --row-tile).
ROW_TILE = None

# parsed MXNET_TPU_FUSED_ROW_TILE, keyed by the raw env string so a
# changed env var between calls still takes effect but the strict
# parse runs once per value, not per kernel invocation
_ROW_TILE_ENV_CACHE = None


def set_row_tile(v):
    """Set the module-wide row-tile knob (None restores the default)."""
    global ROW_TILE
    ROW_TILE = v


def _row_tile_default():
    global _ROW_TILE_ENV_CACHE
    if ROW_TILE is not None:
        return max(1, int(ROW_TILE))
    raw = _config.get("MXNET_TPU_FUSED_ROW_TILE")
    if _ROW_TILE_ENV_CACHE is not None and _ROW_TILE_ENV_CACHE[0] == raw:
        return _ROW_TILE_ENV_CACHE[1]
    if raw in (None, ""):
        val = 16
    else:
        # strict parse: a malformed knob is a job misconfiguration —
        # fail loudly with the knob name, never train on a silently
        # substituted default (the pre-ISSUE-10 read swallowed it)
        val = _config.get_positive_int("MXNET_TPU_FUSED_ROW_TILE")
    _ROW_TILE_ENV_CACHE = (raw, val)
    return val


def _need_interpret(interpret):
    if interpret is not None:
        return interpret
    return kernel_platform() == "cpu"


def _tile_rows(h_out, limit=None):
    """Output rows per grid tile: the largest divisor of H_out <= the
    row-tile knob (default 16)."""
    if limit is None:
        limit = _row_tile_default()
    for cand in range(min(limit, h_out), 0, -1):
        if h_out % cand == 0:
            return cand
    return 1


def _chan_block(c):
    """Output-channel block: 256 lanes when c divides into 256-blocks
    (ResNet channels are powers of two), else the whole axis."""
    if c > 256 and c % 256 == 0:
        return 256
    return c


def _batch_fold(n, per_img_rows, kdim, ndim, per_img_elems):
    """Images folded into the matmul row dimension: the smallest divisor
    ``nb`` of ``n`` whose ``(nb*per_img_rows, kdim) @ (kdim, ndim)``
    matmul meets MXU_WORK_FLOOR, capped so the dominant per-block array
    (``nb*per_img_elems`` elements) stays inside the VMEM budget. When
    even the largest admissible fold misses the floor (tiny test
    shapes), the largest admissible fold is used."""
    best = 1
    for nb in range(1, n + 1):
        if n % nb:
            continue
        if nb > 1 and nb * per_img_elems > _VMEM_BLOCK_ELEMS:
            break
        best = nb
        if nb * per_img_rows * kdim * ndim >= MXU_WORK_FLOOR:
            break
    return best


def _dim_semantics(accumulates):
    """compiler_params for the (channel-block, batch-block, row-tile)
    grid: channel blocks touch disjoint output blocks (parallel); the
    batch/row dims are sequential (arbitrary) whenever they accumulate
    into a revisited output ref (BN stats, dw)."""
    sem = ("parallel",) + (("arbitrary",) * 2 if accumulates
                           else ("parallel",) * 2)
    return pltpu.CompilerParams(dimension_semantics=sem)


def _pad_w(v, left=1, right=1):
    """Zero-pad the W (second-to-last of 4) axis via concat (Mosaic-safe)."""
    nb, rows, _, c = v.shape
    z = jnp.zeros((nb, rows, 1, c), v.dtype)
    parts = [z] * left + [v] + [z] * right
    return jnp.concatenate(parts, axis=2)


def _interleave_zeros(v, axis, offset):
    """Double ``axis`` by interleaving zeros; v lands at offset::2."""
    z = jnp.zeros_like(v)
    pair = (v, z) if offset == 0 else (z, v)
    stacked = jnp.stack(pair, axis=axis + 1)
    shape = list(v.shape)
    shape[axis] *= 2
    return stacked.reshape(shape)


def _subsample2(a, off_r, nr, off_c, nc):
    """``a[:, off_r:off_r+2*nr:2, off_c:off_c+2*nc:2, :]`` for a 4D
    (batch-fold, rows, cols, ch) value, Mosaic-safe: jnp multi-axis
    strided indexing lowers to a >2D gather, which the TPU lowering
    rejects ("Only 2D gather is supported"). Instead take a contiguous
    even-length slice and split ONE spatial axis at a time into
    (count, 2), selecting the parity lane with a static unit index (one
    axis per reshape keeps every intermediate <= 5D). When ``off +
    2*count`` overruns by one (the dy=2 halo case), shift the window
    one left — the selected elements are the same, at parity 1."""
    nb, rows, cols, ch = a.shape
    sr = off_r if off_r + 2 * nr <= rows else off_r - 1
    sc = off_c if off_c + 2 * nc <= cols else off_c - 1
    a = a[:, sr:sr + 2 * nr, sc:sc + 2 * nc, :]
    a = a.reshape(nb, nr, 2, 2 * nc, ch)[:, :, off_r - sr]
    return a.reshape(nb, nr, nc, 2, ch)[:, :, :, off_c - sc]


def _apply_prologue(x, pro, compute_dtype):
    """BN-apply (+ ReLU) on a VMEM-resident value, f32 math."""
    if pro is None:
        return x.astype(compute_dtype)
    scale, bias, relu = pro
    h = x.astype(jnp.float32) * scale + bias
    if relu:
        h = jnp.maximum(h, 0.0)
    return h.astype(compute_dtype)


def _bnbwd_value(e, y_raw, consts):
    """Reconstruct dL/dy from the relu-masked partial ``e`` in VMEM.

    With xhat = (y - mu) * inv_sigma and forward out = gamma*xhat + beta,
    the relu-masked upstream grad e gives
    dL/dy = (gamma * inv_sigma) * (e - m0 - xhat * m1),
    where m0 = mean(e), m1 = mean(e * xhat) over the batch.
    ``consts`` = (k = gamma*inv_sigma, mu, inv_sigma, m0, m1), (1,1,C) f32.
    """
    k, mu, inv_sigma, m0, m1 = consts
    ef = e.astype(jnp.float32)
    xhat = (y_raw.astype(jnp.float32) - mu) * inv_sigma
    return k * (ef - m0 - xhat * m1)


def _nine_shift_matmul(hp, w_ref, th_out, w_out, stride):
    """Core of the 3x3 conv: 9 shifted (nb*th_out*w_out, Ci) @ (Ci, Co)
    matmuls on a W-padded block ``hp`` of shape
    (nb, rows_in, W_out*stride + 2, Ci) — the batch fold rides the row
    dimension, so each MXU call sees the full nb-image tile."""
    nb = hp.shape[0]
    ci = hp.shape[-1]
    co = w_ref.shape[-1]
    acc = jnp.zeros((nb * th_out * w_out, co), jnp.float32)
    for dy in range(3):
        for dx in range(3):
            if stride == 1:
                xs = hp[:, dy:dy + th_out, dx:dx + w_out, :]
            else:
                xs = _subsample2(hp, dy, th_out, dx, w_out)
            acc += jnp.dot(xs.reshape(nb * th_out * w_out, ci), w_ref[dy, dx],
                           preferred_element_type=jnp.float32)
    return acc


def _accumulate_out(ref, value, is_first):
    """Accumulate into an output ref revisited across the whole grid."""
    _accumulate_slot(ref, ..., value, is_first)


def _accumulate_slot(ref, idx, value, is_first):
    """Accumulate into one static (dy, dx) slot of a revisited (k, k, Ci,
    Co) output ref. Writing tap-by-tap keeps peak VMEM at one (Ci, Co)
    partial instead of materializing all k*k taps before the store — the
    stacked form overflowed the 16 MB scoped-vmem limit at 3x3x512x512
    (9.4 MB accumulator + 9.4 MB stacked taps)."""
    @pl.when(is_first)
    def _():
        ref[idx] = value

    @pl.when(jnp.logical_not(is_first))
    def _():
        ref[idx] = ref[idx] + value


def _vec_spec(cdim, blocked=False):
    """(1, 1, C) per-channel constant. ``blocked=True``: C is the
    channel-blocked axis — follow grid dim 0."""
    if blocked:
        return pl.BlockSpec((1, 1, cdim), lambda c_, b_, i_: (0, 0, c_))
    return pl.BlockSpec((1, 1, cdim), lambda c_, b_, i_: (0, 0, 0))


def _mask_halo_rows(hv, i, top_bad, bottom_bad):
    """Zero out-of-image halo rows (padding applies to the normalized
    activation, matching the unfused graph's zero-pad of act). Row axis
    is 1 of the (nb, rows, W, C) block; every folded image shares the
    same tile position, so one row mask covers all nb."""
    rows = hv.shape[1]
    rid = jax.lax.broadcasted_iota(jnp.int32, (1, rows, 1, 1), 1)
    bad = None
    if top_bad:
        bad = jnp.logical_and(i == 0, rid == 0)
    if bottom_bad:
        b = jnp.logical_and(i == pl.num_programs(2) - 1, rid == rows - 1)
        bad = b if bad is None else jnp.logical_or(bad, b)
    if bad is None:
        return hv
    return jnp.where(bad, jnp.zeros_like(hv), hv)


# ---------------------------------------------------------------------------
# blocking plans: one source of truth for kernels, tests, and benchmarks
# ---------------------------------------------------------------------------
def _per_img_conv(th, wo, ci, bco, k, stride):
    """Dominant per-image per-block element count of the conv_fwd/wgrad
    geometry (the VMEM budget term of the batch-fold chooser)."""
    rows_in = stride * th
    wd = wo * stride
    return max((rows_in + (2 if k == 3 else 0)) * wd * ci, th * wo * bco)


def _per_img_dgrad(th_in, th_g, wd, bci, co, k, stride):
    """Dominant per-image per-block element count of conv_dgrad."""
    wo = wd // stride
    return max(th_in * wd * bci, (th_g + 2) * wo * co)


def _plan_conv(n, ho, wo, ci, co, k, stride, row_tile=None,
               chan_block=None, batch_fold=None):
    """Grid plan shared by conv_fwd and conv_wgrad (same geometry):
    (th, ht, rows_in, nb, nbb, bco, cb). ``chan_block``/``batch_fold``
    force a searched schedule's blocks (callers validate divisibility —
    schedule_legal / _schedule_knobs)."""
    # NOT equivalent to _tile_rows(ho, row_tile): tests monkeypatch
    # _tile_rows with a single-arg lambda (test_fused_resnet.py), so
    # the default path must call it with one argument
    th = _tile_rows(ho) if row_tile is None else _tile_rows(ho, row_tile)
    ht = ho // th
    rows_in = stride * th
    bco = chan_block if chan_block else _chan_block(co)
    cb = co // bco
    per_img = _per_img_conv(th, wo, ci, bco, k, stride)
    nb = (batch_fold if batch_fold
          else _batch_fold(n, th * wo, ci, bco, per_img))
    return th, ht, rows_in, nb, n // nb, bco, cb


def _plan_dgrad(n, h, wd, ci, co, k, stride, row_tile=None,
                chan_block=None, batch_fold=None):
    """Grid plan for conv_dgrad: (th_in, ht, th_g, nb, nbb, bci, cib)."""
    # single-arg default call: see the monkeypatch note in _plan_conv
    th_in = _tile_rows(h) if row_tile is None else _tile_rows(h, row_tile)
    if stride == 2 and th_in % 2:
        th_in = 2 if h % 2 == 0 else 1
    ht = h // th_in
    th_g = th_in // stride
    bci = chan_block if chan_block else _chan_block(ci)
    cib = ci // bci
    wo = wd // stride
    rows_img = th_g * wo if k == 1 else th_in * wd
    per_img = _per_img_dgrad(th_in, th_g, wd, bci, co, k, stride)
    nb = (batch_fold if batch_fold
          else _batch_fold(n, rows_img, co, bci, per_img))
    return th_in, ht, th_g, nb, n // nb, bci, cib


def _sched_parts(schedule, row_tile=None):
    s = schedule or {}
    return (s.get("row_tile", row_tile), s.get("chan_block"),
            s.get("batch_fold"))


def mxu_plan(kind, x_shape, w_shape, stride=1, row_tile=None,
             schedule=None):
    """The matmul tile each MXU call sees for a kernel at these shapes.

    kind: 'fwd' | 'wgrad' | 'dgrad'; x_shape: the conv *input* NHWC
    shape; w_shape: (k, k, Ci, Co) HWIO; ``schedule``: an optional
    searched {row_tile, chan_block, batch_fold} to plan instead of the
    hand defaults (the tuner's legality/work oracle). Returns a dict
    with the grid, the per-call matmul dims (m, k, n) and their product
    ``work`` — tests assert ``work >= MXU_WORK_FLOOR`` at real
    ResNet-50 block shapes (the tentpole contract of the round-6
    rewrite)."""
    rt, cbk, bfd = _sched_parts(schedule, row_tile)
    n, h, wd, ci = x_shape
    kk = int(w_shape[0])
    co = int(w_shape[-1])
    if kind in ("fwd", "wgrad"):
        ho, wo = h // stride, wd // stride
        th, ht, rows_in, nb, nbb, bco, cb = _plan_conv(
            n, ho, wo, ci, co, kk, stride, rt, cbk, bfd)
        rows = nb * th * wo
        m, kd, nd = ((rows, ci, bco) if kind == "fwd"
                     else (ci, rows, bco))
        return dict(kind=kind, grid=(cb, nbb, ht), nb=nb, th=th, bco=bco,
                    m=m, k=kd, n=nd, work=m * kd * nd,
                    calls=kk * kk, floor=MXU_WORK_FLOOR)
    if kind == "dgrad":
        th_in, ht, th_g, nb, nbb, bci, cib = _plan_dgrad(
            n, h, wd, ci, co, kk, stride, rt, cbk, bfd)
        rows = nb * (th_g * (wd // stride) if kk == 1 else th_in * wd)
        return dict(kind=kind, grid=(cib, nbb, ht), nb=nb, th=th_in,
                    bco=bci, m=rows, k=co, n=bci, work=rows * co * bci,
                    calls=kk * kk, floor=MXU_WORK_FLOOR)
    raise ValueError("mxu_plan kind must be fwd|wgrad|dgrad, got %r"
                     % (kind,))


def schedule_legal(kind, x_shape, w_shape, stride, schedule):
    """(ok, reason) for a candidate schedule at these shapes — the
    tuner's pre-timing pruning predicate. Rejects tile > dim,
    non-dividing tiles/blocks (they would silently clamp into another
    candidate's plan), odd row tiles under the stride-2 dgrad
    zero-stuffing, and batch folds that overrun the per-block VMEM
    budget."""
    n, h, wd, ci = x_shape
    k = int(w_shape[0])
    co = int(w_shape[-1])
    rt, cbk, bfd = _sched_parts(schedule)
    rows = h if kind == "dgrad" else h // stride
    if rt is not None:
        if rt > rows:
            return False, "row_tile %d > %d output rows" % (rt, rows)
        if rows % rt:
            return False, "row_tile %d does not divide %d rows" % (rt, rows)
        if kind == "dgrad" and stride == 2 and rt % 2:
            return False, "odd row_tile %d with stride-2 dgrad" % rt
    cdim = ci if kind == "dgrad" else co
    if cbk is not None and (cbk > cdim or cdim % cbk):
        return False, "chan_block %d does not tile %d channels" % (cbk, cdim)
    if bfd is not None:
        if bfd > n or n % bfd:
            return False, "batch_fold %d does not tile batch %d" % (bfd, n)
        if bfd > 1:
            th = _tile_rows(rows, rt) if rt is not None else _tile_rows(rows)
            bc = cbk if cbk else _chan_block(cdim)
            if kind == "dgrad":
                per_img = _per_img_dgrad(th, th // stride, wd, bc, co, k,
                                         stride)
            else:
                per_img = _per_img_conv(th, wd // stride, ci, bc, k, stride)
            if bfd * per_img > _VMEM_BLOCK_ELEMS:
                return False, ("batch_fold %d x %d elems overruns the VMEM "
                               "block budget" % (bfd, per_img))
    return True, ""


def _schedule_knobs(kind, key_shape, dtype, schedule, row_tile):
    """Resolve one conv kernel call's (row_tile, chan_block,
    batch_fold). Precedence: explicit ``schedule``/``row_tile`` args
    (the tuner's own timing path and bench sweeps) > the module
    ``ROW_TILE`` global (set_row_tile) > the on-disk schedule table
    (trace-time consult, ISSUE 10) > the hand defaults. A table entry
    that is illegal for the shape (hand-edited/corrupt) counts a
    fallback and yields the defaults — it must never crash a job."""
    if schedule is not None:
        return _sched_parts(schedule, row_tile)
    if row_tile is not None or ROW_TILE is not None \
            or _config.get("MXNET_TPU_FUSED_ROW_TILE") not in (None, ""):
        # every manual override — explicit arg, set_row_tile, or the
        # env knob — pins the hand plan and beats the table (README
        # contract: the knob is the debugging escape hatch)
        return row_tile, None, None
    from ..tune import make_key, schedule_for

    s = schedule_for("fused_" + kind, key_shape, str(dtype))
    if not s:
        return None, None, None
    n, h, wd, ci, co, k, stride = key_shape
    ok, _reason = schedule_legal(kind, (n, h, wd, ci), (k, k, ci, co),
                                 stride, s)
    if not ok:
        import jax

        from .. import profiler

        # overwrite the lookup's per-kernel "table" claim: the stored
        # schedule was REJECTED and the hand defaults ran
        profiler.tuning_record(
            fallbacks=1,
            kernel=make_key("fused_" + kind, key_shape, str(dtype),
                            jax.default_backend()),
            schedule=None, source="fallback_illegal")
        return None, None, None
    return _sched_parts(s)


# ---------------------------------------------------------------------------
# forward conv (k in {1,3}, stride in {1,2}), BN-apply prologue, stats
# epilogue
# ---------------------------------------------------------------------------
def conv_fwd(x, w, *, stride=1, prologue=None, emit_stats=False,
             interpret=None, row_tile=None, schedule=None):
    """NHWC conv: y = conv(act(bn(x)), w).

    x: (N, H, W, Ci); w: (k, k, Ci, Co) with k in {1, 3} (pad = k // 2);
    prologue: None or (scale, bias, relu) with (Ci,) f32 vectors —
    per-channel folded BN apply; emit_stats: additionally return a
    (2, Co) f32 [sum, sum_sq] over the *stored* (dtype-cast) output.
    Returns (y, stats|None). ``schedule``: explicit searched
    {row_tile, chan_block, batch_fold} (the tuner's timing path); when
    absent and no row-tile override is active, the on-disk schedule
    table is consulted at trace time (tune.schedule_for) with the hand
    defaults as fallback.

    Grid: (Co-block, batch-block, row-tile); each kernel instance holds
    ``nb`` images and its matmuls are (nb*th*Wo, Ci) @ (Ci, bco).
    """
    n, h, wd, ci = x.shape
    k = int(w.shape[0])
    co = int(w.shape[-1])
    if stride == 2 and (h % 2 or wd % 2):
        # the unfused conv emits ceil((h-1)/2)+1 rows on odd inputs; the
        # tiled kernels only implement the even case — fail loudly
        # rather than silently computing a different network
        raise ValueError(
            "fused conv: stride-2 requires even spatial dims, got "
            "(%d, %d)" % (h, wd))
    ho, wo = h // stride, wd // stride
    rt, cbk, bfd = _schedule_knobs("fwd", (n, h, wd, ci, co, k, stride),
                                   x.dtype, schedule, row_tile)
    th, ht, rows_in, nb, nbb, bco, cb = _plan_conv(
        n, ho, wo, ci, co, k, stride, rt, cbk, bfd)
    dtype = x.dtype
    has_pro = prologue is not None
    relu = bool(prologue[2]) if has_pro else False

    operands, in_specs = [], []
    if has_pro:
        scale, bias, _ = prologue
        operands += [scale.reshape(1, 1, ci).astype(jnp.float32),
                     bias.reshape(1, 1, ci).astype(jnp.float32)]
        in_specs += [_vec_spec(ci), _vec_spec(ci)]
    nvec = len(operands)

    in_specs.append(pl.BlockSpec((nb, rows_in, wd, ci),
                                 lambda c_, b_, i_: (b_, i_, 0, 0)))
    operands.append(x)
    nx = 1
    if k == 3:
        in_specs.append(pl.BlockSpec(
            (nb, 1, wd, ci),
            lambda c_, b_, i_: (b_, jnp.maximum(rows_in * i_ - 1, 0), 0, 0)))
        operands.append(x)
        nx += 1
        if stride == 1:
            in_specs.append(pl.BlockSpec(
                (nb, 1, wd, ci),
                lambda c_, b_, i_: (b_, jnp.minimum(th * i_ + th, h - 1),
                                    0, 0)))
            operands.append(x)
            nx += 1
    in_specs.append(pl.BlockSpec((k, k, ci, bco),
                                 lambda c_, b_, i_: (0, 0, 0, c_)))
    operands.append(w)

    out_shapes = [jax.ShapeDtypeStruct((n, ho, wo, co), dtype)]
    out_specs = [pl.BlockSpec((nb, th, wo, bco),
                              lambda c_, b_, i_: (b_, i_, 0, c_))]
    if emit_stats:
        out_shapes.append(jax.ShapeDtypeStruct((2, co), jnp.float32))
        out_specs.append(pl.BlockSpec((2, bco), lambda c_, b_, i_: (0, c_)))

    def kernel(*refs):
        vec_refs = refs[:nvec]
        x_refs = refs[nvec:nvec + nx]
        w_ref = refs[nvec + nx]
        y_ref = refs[nvec + nx + 1]
        stats_ref = refs[nvec + nx + 2] if emit_stats else None

        i = pl.program_id(2)
        is_first = jnp.logical_and(pl.program_id(1) == 0, i == 0)
        pro = (vec_refs[0][0], vec_refs[1][0], relu) if has_pro else None

        xc = x_refs[0][...]                          # (nb, rows_in, W, Ci)
        if k == 3:
            parts = [x_refs[1][...], xc]
            if stride == 1:
                parts.append(x_refs[2][...])
            xin = jnp.concatenate(parts, axis=1)
            hv = _apply_prologue(xin, pro, dtype)
            hv = _mask_halo_rows(hv, i, top_bad=True, bottom_bad=(stride == 1))
            hp = _pad_w(hv)
            acc = _nine_shift_matmul(hp, w_ref, th, wo, stride)
        else:
            hv = _apply_prologue(xc, pro, dtype)
            if stride == 2:
                hv = _subsample2(hv, 0, th, 0, wo)
            acc = jnp.dot(hv.reshape(nb * th * wo, ci), w_ref[0, 0],
                          preferred_element_type=jnp.float32)

        y = acc.astype(dtype)
        y_ref[...] = y.reshape(nb, th, wo, bco)
        if emit_stats:
            yf = y.astype(jnp.float32)
            s = jnp.stack([jnp.sum(yf, axis=0), jnp.sum(yf * yf, axis=0)])
            _accumulate_out(stats_ref, s, is_first)

    out = pl.pallas_call(
        kernel,
        grid=(cb, nbb, ht),
        in_specs=in_specs,
        out_specs=out_specs if emit_stats else out_specs[0],
        out_shape=out_shapes if emit_stats else out_shapes[0],
        compiler_params=_dim_semantics(accumulates=emit_stats),
        interpret=_need_interpret(interpret),
    )(*operands)
    return (out[0], out[1]) if emit_stats else (out, None)


# ---------------------------------------------------------------------------
# weight gradient: dw = sum_pixels act(bn(x))^T (.) g, with the BN backward
# reconstruction of g riding the g-side read
# ---------------------------------------------------------------------------
def conv_wgrad(x, g_parts, w_shape, *, stride=1, x_prologue=None,
               g_bnbwd=None, interpret=None, row_tile=None, schedule=None):
    """dw for conv_fwd, accumulated f32 across the whole grid.

    x: (N, H, W, Ci) raw input; g_parts: the complete output gradient
    (N, Ho, Wo, Co) when ``g_bnbwd`` is None, else ``(e, y_raw)`` from
    which dL/dy is reconstructed per tile (see _bnbwd_value);
    w_shape: (k, k, Ci, Co); x_prologue: (scale, bias, relu) BN-apply
    consts for the x side; ``schedule``: see conv_fwd (table key
    ``fused_wgrad``).

    Grid: (Co-block, batch-block, row-tile) — Co-block outermost so the
    revisited f32 dw accumulator stays VMEM-resident across the whole
    (batch, row) sweep; the batch fold rides the matmul *contraction*
    dim: each call is (Ci, nb*th*Wo) @ (nb*th*Wo, bco).
    """
    n, h, wd, ci = x.shape
    k = int(w_shape[0])
    co = int(w_shape[-1])
    ho, wo = h // stride, wd // stride
    rt, cbk, bfd = _schedule_knobs("wgrad", (n, h, wd, ci, co, k, stride),
                                   x.dtype, schedule, row_tile)
    th, ht, rows_in, nb, nbb, bco, cb = _plan_conv(
        n, ho, wo, ci, co, k, stride, rt, cbk, bfd)
    dtype = x.dtype
    has_xpro = x_prologue is not None
    x_relu = bool(x_prologue[2]) if has_xpro else False

    operands, in_specs = [], []
    if has_xpro:
        operands += [x_prologue[0].reshape(1, 1, ci).astype(jnp.float32),
                     x_prologue[1].reshape(1, 1, ci).astype(jnp.float32)]
        in_specs += [_vec_spec(ci), _vec_spec(ci)]
    n_xvec = len(operands)
    if g_bnbwd is not None:
        operands += [c.reshape(1, 1, co).astype(jnp.float32) for c in g_bnbwd]
        in_specs += [_vec_spec(bco, blocked=True)] * 5
    nvec = len(operands)

    in_specs.append(pl.BlockSpec((nb, rows_in, wd, ci),
                                 lambda c_, b_, i_: (b_, i_, 0, 0)))
    operands.append(x)
    nx = 1
    if k == 3:
        in_specs.append(pl.BlockSpec(
            (nb, 1, wd, ci),
            lambda c_, b_, i_: (b_, jnp.maximum(rows_in * i_ - 1, 0), 0, 0)))
        operands.append(x)
        nx += 1
        if stride == 1:
            in_specs.append(pl.BlockSpec(
                (nb, 1, wd, ci),
                lambda c_, b_, i_: (b_, jnp.minimum(th * i_ + th, h - 1),
                                    0, 0)))
            operands.append(x)
            nx += 1
    g_spec = pl.BlockSpec((nb, th, wo, bco),
                          lambda c_, b_, i_: (b_, i_, 0, c_))
    if g_bnbwd is None:
        in_specs.append(g_spec)
        operands.append(g_parts)
        n_g = 1
    else:
        in_specs += [g_spec, g_spec]
        operands += [g_parts[0], g_parts[1]]
        n_g = 2

    def kernel(*refs):
        vec_refs = refs[:nvec]
        x_refs = refs[nvec:nvec + nx]
        g_refs = refs[nvec + nx:nvec + nx + n_g]
        dw_ref = refs[nvec + nx + n_g]

        i = pl.program_id(2)
        is_first = jnp.logical_and(pl.program_id(1) == 0, i == 0)
        pro = (vec_refs[0][0], vec_refs[1][0], x_relu) if has_xpro else None

        if g_bnbwd is None:
            g_val = g_refs[0][...].astype(jnp.float32)
        else:
            consts = tuple(vec_refs[n_xvec + j][...] for j in range(5))
            g_val = _bnbwd_value(g_refs[0][...], g_refs[1][...], consts)
        gf = g_val.reshape(nb * th * wo, bco).astype(dtype)

        xc = x_refs[0][...]
        if k == 3:
            parts = [x_refs[1][...], xc]
            if stride == 1:
                parts.append(x_refs[2][...])
            xin = jnp.concatenate(parts, axis=1)
            hv = _apply_prologue(xin, pro, dtype)
            hv = _mask_halo_rows(hv, i, top_bad=True, bottom_bad=(stride == 1))
            hp = _pad_w(hv)
            for dy in range(3):
                for dx in range(3):
                    if stride == 1:
                        xs = hp[:, dy:dy + th, dx:dx + wo, :]
                    else:
                        xs = _subsample2(hp, dy, th, dx, wo)
                    cur = jax.lax.dot_general(
                        xs.reshape(nb * th * wo, ci), gf,
                        dimension_numbers=(((0,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    _accumulate_slot(dw_ref, (dy, dx), cur, is_first)
        else:
            hv = _apply_prologue(xc, pro, dtype)
            if stride == 2:
                hv = _subsample2(hv, 0, th, 0, wo)
            dw = jax.lax.dot_general(
                hv.reshape(nb * th * wo, ci), gf,
                dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32).reshape(1, 1, ci, bco)
            _accumulate_out(dw_ref, dw, is_first)

    return pl.pallas_call(
        kernel,
        grid=(cb, nbb, ht),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((k, k, ci, bco),
                               lambda c_, b_, i_: (0, 0, 0, c_)),
        out_shape=jax.ShapeDtypeStruct((k, k, ci, co), jnp.float32),
        compiler_params=_dim_semantics(accumulates=True),
        interpret=_need_interpret(interpret),
    )(*operands)


# ---------------------------------------------------------------------------
# data gradient: e_out = mask(y_in) * (g (*) w^T), plus (dbeta, dgamma)
# accumulation — the BN-backward input-side partial for the next layer down
# ---------------------------------------------------------------------------
def conv_dgrad(g_parts, w, x_shape, *, stride=1, g_bnbwd=None,
               out_mask=None, extra=None, interpret=None, row_tile=None,
               schedule=None):
    """Input gradient of conv_fwd with fused epilogue.

    g_parts: complete gradient (N, Ho, Wo, Co), or ``(e, y_raw)`` with
    ``g_bnbwd`` consts; w: (k, k, Ci, Co); x_shape: (N, H, W, Ci).

    out_mask: None → returns (dx, None) with raw dL/dx. Or (y_in,
    gamma, beta, mu, inv_sigma) — the conv input's own BN: computes
    mask = (gamma*xhat + beta > 0), returns (e_out, stats) where
    e_out = mask * dL/dact and stats is (2, Ci) f32
    [sum(e_out), sum(e_out*xhat)] = (dbeta, dgamma) of that BN.

    extra: optional (g2, w2, stride2) second 1x1-conv contribution
    added to dL/dact before masking (the downsample unit's shortcut
    join at act1); g2 is a complete gradient at stride2 resolution.

    Grid: (Ci-block, batch-block, row-tile); the batch fold rides the
    matmul row dimension: each call is (nb*rows, Co) @ (Co, bci);
    ``schedule``: see conv_fwd (table key ``fused_dgrad``).
    """
    n, h, wd, ci = x_shape
    k = int(w.shape[0])
    co = int(w.shape[-1])
    ho, wo = h // stride, wd // stride
    rt, cbk, bfd = _schedule_knobs("dgrad", (n, h, wd, ci, co, k, stride),
                                   w.dtype, schedule, row_tile)
    th_in, ht, th_g, nb, nbb, bci, cib = _plan_dgrad(
        n, h, wd, ci, co, k, stride, rt, cbk, bfd)
    dtype = w.dtype

    # flipped, io-transposed kernel: dgrad = conv(g_stuffed, wflip)
    wflip = jnp.flip(jnp.flip(w, 0), 1).transpose(0, 1, 3, 2)  # (k,k,Co,Ci)

    operands, in_specs = [], []
    if g_bnbwd is not None:
        operands += [c.reshape(1, 1, co).astype(jnp.float32) for c in g_bnbwd]
        in_specs += [_vec_spec(co)] * 5
    n_gvec = len(operands)
    if out_mask is not None:
        y_in, m_gamma, m_beta, m_mu, m_inv = out_mask
        operands += [v.reshape(1, 1, ci).astype(jnp.float32)
                     for v in (m_gamma, m_beta, m_mu, m_inv)]
        in_specs += [_vec_spec(bci, blocked=True)] * 4
    nvec = len(operands)

    halo_top = k == 3 and stride == 1
    halo_bot = k == 3                       # s2 zero-stuff needs g[h0+th_g]
    n_g_blocks = 1 + int(halo_top) + int(halo_bot)
    g_ops = [g_parts] if g_bnbwd is None else [g_parts[0], g_parts[1]]
    for op in g_ops:
        in_specs.append(pl.BlockSpec((nb, th_g, wo, co),
                                     lambda c_, b_, i_: (b_, i_, 0, 0)))
        operands.append(op)
        if halo_top:
            in_specs.append(pl.BlockSpec(
                (nb, 1, wo, co),
                lambda c_, b_, i_: (b_, jnp.maximum(th_g * i_ - 1, 0), 0, 0)))
            operands.append(op)
        if halo_bot:
            in_specs.append(pl.BlockSpec(
                (nb, 1, wo, co),
                lambda c_, b_, i_: (b_, jnp.minimum(th_g * i_ + th_g, ho - 1),
                                    0, 0)))
            operands.append(op)

    in_specs.append(pl.BlockSpec((k, k, co, bci),
                                 lambda c_, b_, i_: (0, 0, 0, c_)))
    operands.append(wflip)
    if extra is not None:
        g2, w2, s2 = extra
        co2 = int(w2.shape[-1])
        w2t = w2.reshape(ci, co2).T.astype(dtype)            # (Co2, Ci)
        th_g2 = th_in // s2
        in_specs.append(pl.BlockSpec((nb, th_g2, wd // s2, co2),
                                     lambda c_, b_, i_: (b_, i_, 0, 0)))
        operands.append(g2)
        in_specs.append(pl.BlockSpec((co2, bci),
                                     lambda c_, b_, i_: (0, c_)))
        operands.append(w2t)
    if out_mask is not None:
        in_specs.append(pl.BlockSpec((nb, th_in, wd, bci),
                                     lambda c_, b_, i_: (b_, i_, 0, c_)))
        operands.append(y_in)

    out_shapes = [jax.ShapeDtypeStruct((n, h, wd, ci), dtype)]
    out_specs = [pl.BlockSpec((nb, th_in, wd, bci),
                              lambda c_, b_, i_: (b_, i_, 0, c_))]
    if out_mask is not None:
        out_shapes.append(jax.ShapeDtypeStruct((2, ci), jnp.float32))
        out_specs.append(pl.BlockSpec((2, bci), lambda c_, b_, i_: (0, c_)))

    def kernel(*refs):
        pos = 0
        vec_refs = refs[pos:pos + nvec]; pos += nvec
        g_refs = refs[pos:pos + len(g_ops) * n_g_blocks]
        pos += len(g_ops) * n_g_blocks
        w_ref = refs[pos]; pos += 1
        if extra is not None:
            g2_ref, w2_ref = refs[pos], refs[pos + 1]
            pos += 2
        if out_mask is not None:
            yin_ref = refs[pos]; pos += 1
        e_ref = refs[pos]; pos += 1
        stats_ref = refs[pos] if out_mask is not None else None

        i = pl.program_id(2)
        is_first = jnp.logical_and(pl.program_id(1) == 0, i == 0)

        # assemble g (center + halo rows), reconstructing dL/dy per block
        if g_bnbwd is None:
            parts = [g_refs[j][...].astype(jnp.float32)
                     for j in range(n_g_blocks)]
        else:
            consts = tuple(vec_refs[j][...] for j in range(5))
            parts = [_bnbwd_value(g_refs[j][...], g_refs[n_g_blocks + j][...],
                                  consts)
                     for j in range(n_g_blocks)]
        center, halos = parts[0], parts[1:]

        if k == 1:
            gm = center.reshape(nb * th_g * wo, co).astype(dtype)
            m = jnp.dot(gm, w_ref[0, 0], preferred_element_type=jnp.float32)
            if stride == 1:
                t = m.reshape(nb, th_in, wd, bci)
            else:
                m4 = m.reshape(nb, th_g, wo, bci)
                t = _interleave_zeros(
                    _interleave_zeros(m4, axis=2, offset=0), axis=1, offset=0)
        else:
            if stride == 1:
                top = jnp.where(i == 0, jnp.zeros_like(halos[0]), halos[0])
                bot = jnp.where(i == pl.num_programs(2) - 1,
                                jnp.zeros_like(halos[1]), halos[1])
                gin = jnp.concatenate([top, center, bot], axis=1)
                gp = _pad_w(gin.astype(dtype))
                t = _nine_shift_matmul(gp, w_ref, th_in, wd, 1)
                t = t.reshape(nb, th_in, wd, bci)
            else:
                # transposed conv via zero-stuffing: gz[2h+1-P0, 2w+1] =
                # g[h, w] on a (th_in+2, W+2) tile; then a plain 3x3 s1
                # sweep with the flipped kernel (see derivation in tests)
                bot = jnp.where(i == pl.num_programs(2) - 1,
                                jnp.zeros_like(halos[0]), halos[0])
                g_ext = jnp.concatenate([center, bot], axis=1)  # (nb,th_g+1,)
                rows = _interleave_zeros(g_ext, axis=1, offset=1)
                z = _interleave_zeros(rows, axis=2, offset=1)
                z = jnp.concatenate(
                    [z, jnp.zeros((nb, z.shape[1], 2, co), z.dtype)], axis=2)
                t = _nine_shift_matmul(z.astype(dtype), w_ref, th_in, wd, 1)
                t = t.reshape(nb, th_in, wd, bci)

        if extra is not None:
            g2v = g2_ref[...]
            s2 = extra[2]
            m2 = jnp.dot(g2v.reshape(-1, co2).astype(dtype), w2_ref[...],
                         preferred_element_type=jnp.float32)
            if s2 == 1:
                t = t + m2.reshape(nb, th_in, wd, bci)
            else:
                m4 = m2.reshape(nb, th_in // s2, wd // s2, bci)
                t = t + _interleave_zeros(
                    _interleave_zeros(m4, axis=2, offset=0), axis=1, offset=0)

        if out_mask is None:
            e_ref[...] = t.astype(dtype)
        else:
            gmma = vec_refs[n_gvec][...]
            beta = vec_refs[n_gvec + 1][...]
            mu = vec_refs[n_gvec + 2][...]
            inv = vec_refs[n_gvec + 3][...]
            xhat = (yin_ref[...].astype(jnp.float32) - mu) * inv
            mask = (gmma * xhat + beta) > 0
            e_out = jnp.where(mask, t, 0.0)
            e_ref[...] = e_out.astype(dtype)
            ef = e_out.reshape(nb * th_in * wd, bci)
            xf = xhat.reshape(nb * th_in * wd, bci)
            s = jnp.stack([jnp.sum(ef, axis=0), jnp.sum(ef * xf, axis=0)])
            _accumulate_out(stats_ref, s, is_first)

    out = pl.pallas_call(
        kernel,
        grid=(cib, nbb, ht),
        in_specs=in_specs,
        out_specs=out_specs if out_mask is not None else out_specs[0],
        out_shape=out_shapes if out_mask is not None else out_shapes[0],
        compiler_params=_dim_semantics(accumulates=out_mask is not None),
        interpret=_need_interpret(interpret),
    )(*operands)
    return (out[0], out[1]) if out_mask is not None else (out, None)


# ---------------------------------------------------------------------------
# bottleneck-unit composition (ResNet v2 pre-activation), custom VJP
# ---------------------------------------------------------------------------
def _bn_consts(gamma, beta, mean, inv):
    """Fold (gamma, beta, mean, inv_sigma) into apply (scale, bias)."""
    scale = gamma.astype(jnp.float32) * inv
    bias = beta.astype(jnp.float32) - mean * scale
    return scale, bias


def _finalize_stats(stats, count, eps):
    mean = stats[0] / count
    var = jnp.maximum(stats[1] / count - mean * mean, 0.0)
    return mean, var, jax.lax.rsqrt(var + eps)


def _unit_fwd(data, w1, w2, w3, wsc, g1, b1, g2, b2, g3, b3,
              stride, eps, interpret, axis=None, axis_size=1):
    """Training forward. Weights HWIO; data NHWC. Returns out, batch
    stats (mean/var per BN), and the VJP residuals.

    ``axis``: when run inside ``shard_map`` with the batch sharded over
    mesh axes ``axis``, the BN statistic sums are psum'd over it (global
    batch statistics — the same semantics the unfused pjit graph gets
    from XLA partitioning its batch reductions) and counts are scaled by
    the static ``axis_size``.
    """
    n, h, wd, _ci = data.shape
    n1 = n * h * wd * axis_size
    xf = data.astype(jnp.float32)
    s0 = jnp.sum(xf, axis=(0, 1, 2))
    s1 = jnp.sum(xf * xf, axis=(0, 1, 2))
    s01 = jnp.stack([s0, s1])
    if axis is not None:
        s01 = jax.lax.psum(s01, axis)
    mean1, var1, inv1 = _finalize_stats(s01, n1, eps)
    sc1, bi1 = _bn_consts(g1, b1, mean1, inv1)

    y1, st1 = conv_fwd(data, w1, stride=1, prologue=(sc1, bi1, True),
                       emit_stats=True, interpret=interpret)
    if axis is not None:
        st1 = jax.lax.psum(st1, axis)
    mean2, var2, inv2 = _finalize_stats(st1, n1, eps)
    sc2, bi2 = _bn_consts(g2, b2, mean2, inv2)

    y2, st2 = conv_fwd(y1, w2, stride=stride, prologue=(sc2, bi2, True),
                       emit_stats=True, interpret=interpret)
    if axis is not None:
        st2 = jax.lax.psum(st2, axis)
    n2 = n * (h // stride) * (wd // stride) * axis_size
    mean3, var3, inv3 = _finalize_stats(st2, n2, eps)
    sc3, bi3 = _bn_consts(g3, b3, mean3, inv3)

    y3, _ = conv_fwd(y2, w3, stride=1, prologue=(sc3, bi3, True),
                     emit_stats=False, interpret=interpret)
    if wsc is None:
        shortcut = data
    else:
        shortcut, _ = conv_fwd(data, wsc, stride=stride,
                               prologue=(sc1, bi1, True), interpret=interpret)
    out = y3 + shortcut
    stats = (mean1, var1, mean2, var2, mean3, var3)
    res = (data, y1, y2, w1, w2, w3, wsc, g1, b1, g2, b2, g3, b3,
           mean1, inv1, mean2, inv2, mean3, inv3)
    return out, stats, res


def _unit_bwd(stride, eps, interpret, res, g, axis=None, axis_size=1):
    (data, y1, y2, w1, w2, w3, wsc, g1, b1, g2, b2, g3, b3,
     mean1, inv1, mean2, inv2, mean3, inv3) = res
    n, h, wd, _ci = data.shape
    n1 = float(n * h * wd * axis_size)
    n2 = float(n * (h // stride) * (wd // stride) * axis_size)

    def _allreduce(v):
        return v if axis is None else jax.lax.psum(v, axis)

    sc1, bi1 = _bn_consts(g1, b1, mean1, inv1)
    sc2, bi2 = _bn_consts(g2, b2, mean2, inv2)
    sc3, bi3 = _bn_consts(g3, b3, mean3, inv3)

    # conv3 (1x1 s1): dgrad emits e2 = mask3 * dact3 and (dbeta3, dgamma3)
    e2, st3 = conv_dgrad(g, w3, y2.shape, stride=1,
                         out_mask=(y2, g3, b3, mean3, inv3),
                         interpret=interpret)
    st3 = _allreduce(st3)
    dbeta3, dgamma3 = st3[0], st3[1]
    dw3 = conv_wgrad(y2, g, w3.shape, stride=1,
                     x_prologue=(sc3, bi3, True), interpret=interpret)
    cb2 = (g3.astype(jnp.float32) * inv3, mean3, inv3,
           dbeta3 / n2, dgamma3 / n2)

    # conv2 (3x3, stride): g side reconstructed from (e2, y2) via bn3 bwd
    dw2 = conv_wgrad(y1, (e2, y2), w2.shape, stride=stride,
                     x_prologue=(sc2, bi2, True), g_bnbwd=cb2,
                     interpret=interpret)
    e1, st2 = conv_dgrad((e2, y2), w2, y1.shape, stride=stride, g_bnbwd=cb2,
                         out_mask=(y1, g2, b2, mean2, inv2),
                         interpret=interpret)
    st2 = _allreduce(st2)
    dbeta2, dgamma2 = st2[0], st2[1]
    cb1 = (g2.astype(jnp.float32) * inv2, mean2, inv2,
           dbeta2 / n1, dgamma2 / n1)

    # conv1 (1x1 s1): the downsample shortcut joins at act1 (extra term)
    dw1 = conv_wgrad(data, (e1, y1), w1.shape, stride=1,
                     x_prologue=(sc1, bi1, True), g_bnbwd=cb1,
                     interpret=interpret)
    extra = None if wsc is None else (g, wsc, stride)
    e0, st1 = conv_dgrad((e1, y1), w1, data.shape, stride=1, g_bnbwd=cb1,
                         out_mask=(data, g1, b1, mean1, inv1), extra=extra,
                         interpret=interpret)
    st1 = _allreduce(st1)
    dbeta1, dgamma1 = st1[0], st1[1]

    # weight grads: each shard holds its batch slice's contribution;
    # under shard_map the all-reduce happens here (f32, pre-cast) so the
    # replicated out_specs of the spmd wrapper are genuinely replicated
    dw1, dw2, dw3 = _allreduce(dw1), _allreduce(dw2), _allreduce(dw3)
    dwsc = None
    if wsc is not None:
        dwsc = _allreduce(conv_wgrad(
            data, g, wsc.shape, stride=stride,
            x_prologue=(sc1, bi1, True),
            interpret=interpret)).astype(wsc.dtype)

    # bn1 backward to the unit input (elementwise; XLA fuses it with the
    # dim-match shortcut add)
    xhat0 = (data.astype(jnp.float32) - mean1) * inv1
    ddata = (g1.astype(jnp.float32) * inv1) * (
        e0.astype(jnp.float32) - dbeta1 / n1 - xhat0 * (dgamma1 / n1))
    if wsc is None:
        ddata = ddata + g.astype(jnp.float32)
    ddata = ddata.astype(data.dtype)

    return (ddata, dw1.astype(w1.dtype), dw2.astype(w2.dtype),
            dw3.astype(w3.dtype), dwsc,
            dgamma1.astype(g1.dtype), dbeta1.astype(b1.dtype),
            dgamma2.astype(g2.dtype), dbeta2.astype(b2.dtype),
            dgamma3.astype(g3.dtype), dbeta3.astype(b3.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(11, 12, 13))
def bottleneck_train(data, w1, w2, w3, wsc, g1, b1, g2, b2, g3, b3,
                     stride, eps, interpret):
    """Fused pre-activation bottleneck unit, training mode.

    Returns (out, (mean1, var1, mean2, var2, mean3, var3)) — the batch
    statistics feed the caller's moving-stat update (stop-gradient
    them; they carry no cotangent).
    """
    out, stats, _ = _unit_fwd(data, w1, w2, w3, wsc, g1, b1, g2, b2, g3, b3,
                              stride, eps, interpret)
    return out, stats


def _bottleneck_train_fwd(data, w1, w2, w3, wsc, g1, b1, g2, b2, g3, b3,
                          stride, eps, interpret):
    out, stats, res = _unit_fwd(data, w1, w2, w3, wsc, g1, b1, g2, b2,
                                g3, b3, stride, eps, interpret)
    return (out, stats), res


def _bottleneck_train_bwd(stride, eps, interpret, res, cotangents):
    g, _gstats = cotangents
    return _unit_bwd(stride, eps, interpret, res, g)


bottleneck_train.defvjp(_bottleneck_train_fwd, _bottleneck_train_bwd)


def bottleneck_infer(data, w1, w2, w3, wsc, g1, b1, g2, b2, g3, b3,
                     mm1, mv1, mm2, mv2, mm3, mv3, *, stride, eps,
                     interpret=None):
    """Inference mode: BN applies use the moving statistics."""
    def consts(gm, bt, mm, mv):
        inv = jax.lax.rsqrt(mv.astype(jnp.float32) + eps)
        return _bn_consts(gm, bt, mm.astype(jnp.float32), inv)

    p1 = consts(g1, b1, mm1, mv1) + (True,)
    y1, _ = conv_fwd(data, w1, stride=1, prologue=p1, interpret=interpret)
    p2 = consts(g2, b2, mm2, mv2) + (True,)
    y2, _ = conv_fwd(y1, w2, stride=stride, prologue=p2, interpret=interpret)
    p3 = consts(g3, b3, mm3, mv3) + (True,)
    y3, _ = conv_fwd(y2, w3, stride=1, prologue=p3, interpret=interpret)
    if wsc is None:
        shortcut = data
    else:
        shortcut, _ = conv_fwd(data, wsc, stride=stride, prologue=p1,
                               interpret=interpret)
    return y3 + shortcut


# ---------------------------------------------------------------------------
# multi-chip: explicit shard_map partitioning of the Pallas kernels
# ---------------------------------------------------------------------------
# pjit can freely partition the *interpret-mode* fused graph (it is plain
# jax ops), but real Mosaic kernels are opaque to the partitioner: on TPU
# the batch-sharded fused step must place each kernel inside shard_map
# with the batch axis manual. The wrappers below do that with an explicit
# custom VJP — fwd and bwd are each their own shard_map region, and every
# cross-shard reduction (BN statistic sums, weight grads) is an explicit
# psum over the data axes, so ``check_vma=False`` is sound. Reference
# counterpart of the reduction this replaces: src/kvstore/comm.h:484-690
# (device-tree gradient reduce); here it rides ICI inside the step.

_SPMD_SCOPE = threading.local()


@contextlib.contextmanager
def spmd_scope(mesh, axes):
    """Trace-time marker: fused ops built inside this scope partition
    their Pallas kernels over ``mesh`` with the batch sharded on mesh
    axes ``axes`` (via shard_map). Set by TrainStep around its step
    invocation; consulted by ops/fused.py at trace time."""
    prev = getattr(_SPMD_SCOPE, "value", None)
    _SPMD_SCOPE.value = (mesh, tuple(axes))
    try:
        yield
    finally:
        _SPMD_SCOPE.value = prev


def current_spmd_scope():
    return getattr(_SPMD_SCOPE, "value", None)


def _spmd_parts(mesh, axes):
    ax = tuple(axes)
    asize = int(_np.prod([mesh.shape[a] for a in ax]))
    dspec = P(ax if len(ax) > 1 else ax[0], None, None, None)
    return ax, asize, dspec


_RES_NSHARDED = 3   # res = (data, y1, y2, then 16 replicated leaves)
_RES_NREP = 16


def _res_specs(dspec):
    return (dspec,) * _RES_NSHARDED + (P(),) * _RES_NREP


@functools.partial(jax.custom_vjp, nondiff_argnums=(11, 12, 13, 14, 15))
def bottleneck_train_spmd(data, w1, w2, w3, wsc, g1, b1, g2, b2, g3, b3,
                          stride, eps, interpret, mesh, axes):
    """``bottleneck_train`` with the batch sharded over mesh ``axes``.

    Same math and return convention as :func:`bottleneck_train` with
    global-batch BN statistics (matching what XLA's partitioner gives
    the unfused graph); out is sharded like data, stats/weight grads
    are replicated.
    """
    (out, stats), _ = _spmd_train_fwd(data, w1, w2, w3, wsc, g1, b1, g2, b2,
                                      g3, b3, stride, eps, interpret, mesh,
                                      axes)
    return out, stats


def _spmd_train_fwd(data, w1, w2, w3, wsc, g1, b1, g2, b2, g3, b3,
                    stride, eps, interpret, mesh, axes):
    ax, asize, dspec = _spmd_parts(mesh, axes)
    rep = P()

    def local(data, w1, w2, w3, wsc, g1, b1, g2, b2, g3, b3):
        return _unit_fwd(data, w1, w2, w3, wsc, g1, b1, g2, b2, g3, b3,
                         stride, eps, interpret, axis=ax, axis_size=asize)

    f = _shard_map(
        local, mesh=mesh,
        in_specs=(dspec,) + (rep,) * 10,
        out_specs=(dspec, (rep,) * 6, _res_specs(dspec)),
        check_vma=False)
    out, stats, res = f(data, w1, w2, w3, wsc, g1, b1, g2, b2, g3, b3)
    return (out, stats), res


def _spmd_train_bwd(stride, eps, interpret, mesh, axes, res, cotangents):
    g, _gstats = cotangents
    ax, asize, dspec = _spmd_parts(mesh, axes)
    rep = P()

    def local(res, g):
        return _unit_bwd(stride, eps, interpret, res, g,
                         axis=ax, axis_size=asize)

    f = _shard_map(
        local, mesh=mesh,
        in_specs=(_res_specs(dspec), dspec),
        out_specs=(dspec,) + (rep,) * 10,
        check_vma=False)
    return f(res, g)


bottleneck_train_spmd.defvjp(_spmd_train_fwd, _spmd_train_bwd)


def bottleneck_infer_spmd(data, w1, w2, w3, wsc, g1, b1, g2, b2, g3, b3,
                          mm1, mv1, mm2, mv2, mm3, mv3, *, stride, eps,
                          mesh, axes, interpret=None):
    """``bottleneck_infer`` with the batch sharded over mesh ``axes``.

    Inference uses the moving statistics, so the computation is purely
    per-sample: a plain forward shard_map with no collectives."""
    _ax, _asize, dspec = _spmd_parts(mesh, axes)
    rep = P()

    def local(*args):
        return bottleneck_infer(*args, stride=stride, eps=eps,
                                interpret=interpret)

    f = _shard_map(local, mesh=mesh,
                      in_specs=(dspec,) + (rep,) * 16,
                      out_specs=dspec, check_vma=False)
    return f(data, w1, w2, w3, wsc, g1, b1, g2, b2, g3, b3,
             mm1, mv1, mm2, mv2, mm3, mv3)
