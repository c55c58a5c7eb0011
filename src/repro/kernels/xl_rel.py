"""Pallas TPU kernel for Transformer-XL's relative-position term, already shifted.

Transformer-XL scores a query against a key by their content (the AC term)
and by their distance (the BD term). Written out on the causal-valid region
``j <= (sk - sq) + i``:

    bd[b, h, i, j] = qv[b, i, h] . r[j + sq - 1 - i, h]

with ``qv = q + v_bias`` and ``r`` the projected sinusoids of distances
``sk - 1 .. 0``. The XLA path (models/attention.py) computes the unshifted
product ``qv @ r^T`` and shifts it with a pad, two reshapes and a slice of the
whole (B, H, sq, sk) score tensor in HBM; here the shift happens in VMEM.

Per (head, batch, query tile of TQ rows) the kernel computes
``P = qv_tile @ R^T`` over ``R``, the head's ``r`` zero-padded to ``W`` rows,
a lane multiple ``>= sk``. Row ``t`` (global query ``i``) then needs column
``j + sq - 1 - i`` of P, a rotation that grows by one per row: one strided
``pltpu.roll``. On the valid region the column stays below ``sk`` and never
wraps; elsewhere it may wrap onto another column or a zero row of R, which is
finite, as the caller's mask requires.

The backward zeroes what the forward never wrote (columns ``>= sk``, rows
``>= sq``) and rolls each row of the cotangent ``g`` the other way, by
``sq - 1 - i``. That rotation falls by one per row, and a strided roll only
rises, so the tile's rows are first reversed on the MXU. Then
``dqv = gP @ R`` and ``dR += gP^T @ qv``, accumulated over batch and query
tiles in the head's resident f32 dR block. Neither the unshifted product nor
the unshifted cotangent exists in HBM.

Blocks run past the arrays where ``sq`` or ``sk`` is not tile-aligned: the
output's out-of-bounds part is dropped, the inputs' is undefined and masked.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import round_up

LANE = 128
MAX_TQ = 256
# Rows of an f32 vreg. Mosaic's strided roll gives wrong rows where the
# rotations within one 8-row group cross a multiple of 128 (seen on a v5e:
# rows 124-127 of a stride-1 roll by 5 over (176, 1152) came out wrong). So
# every roll here starts each 8-row group at a multiple of 8: the static
# rest of the rotation is moved into the rows of R, a cyclic roll of a
# small operand (see ``_split``).
SUBLANE = 8


def _tiles(sq: int, sk: int):
    """(TQ, n query tiles, W): query tiles split sq evenly into 16-row
    multiples of at most MAX_TQ rows; W is sk padded to whole lanes."""
    n = pl.cdiv(sq, MAX_TQ)
    tq = round_up(pl.cdiv(sq, n), 16)
    return tq, pl.cdiv(sq, tq), round_up(sk, LANE)


def _split(c: int):
    """A static rotation c as (multiple of SUBLANE, rest)."""
    return c - c % SUBLANE, c % SUBLANE


def _fwd_kernel(qv_ref, r_ref, o_ref, *, base: int, tq: int, w: int):
    # qv_ref (1, 1, TQ, D); r_ref (1, W, D) R's rows rolled by the rest of
    # _split((1 - sq) % W); o_ref (1, 1, TQ, W)
    i0 = pl.program_id(2) * tq
    p = jax.lax.dot_general(qv_ref[0, 0], r_ref[0], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (TQ, W)
    # row t of the tile (query i = i0 + t) rolls right by i - (sq - 1), of
    # which R's rows took the rest
    shift = (i0 + base) % w
    o_ref[0, 0] = pltpu.roll(p, shift, 1, stride=1,
                             stride_axis=0).astype(o_ref.dtype)


def _bwd_kernel(g_ref, qv_ref, r_ref, dqv_ref, dr_ref, *, sq: int, sk: int,
                base: int, tq: int, w: int):
    # g_ref (1, 1, TQ, W); qv_ref/dqv_ref (1, 1, TQ, D); r_ref (1, W, D) R's
    # rows rolled back by the rest of _split((sq - TQ) % W); dr_ref
    # (1, W, D) f32 in the same rolled rows, resident over the batch and
    # query-tile axes
    b, qi = pl.program_id(1), pl.program_id(2)
    i0 = qi * tq

    @pl.when((b == 0) & (qi == 0))
    def _():
        dr_ref[...] = jnp.zeros_like(dr_ref)

    dtype = qv_ref.dtype
    g = g_ref[0, 0]
    row = jax.lax.broadcasted_iota(jnp.int32, g.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, g.shape, 1)
    g = jnp.where((row < sq - i0) & (col < sk), g, jnp.zeros_like(g))
    qv = qv_ref[0, 0]
    qv = jnp.where(jax.lax.broadcasted_iota(jnp.int32, qv.shape, 0) < sq - i0,
                   qv, jnp.zeros_like(qv))
    # Row t of g (query i = i0 + t) must roll right by sq - 1 - i, which
    # falls by one per row. The strided roll only rises: Mosaic does not
    # reduce a stride of W - 1 modulo W (it refuses one with a static shift
    # and gives wrong rows with a dynamic one). So the tile's rows are
    # reversed first, by the exchange matrix J on the MXU (exact: one
    # product by 1 per output), and rolled right by sq - i0 - TQ + u for row
    # u = TQ - 1 - t (R's rows took the rest): gr = J @ gP, with
    # gP[t, m] = g[t, m + i - (sq - 1)].
    ju = jax.lax.broadcasted_iota(jnp.int32, (tq, tq), 0)
    flip = (ju + jax.lax.broadcasted_iota(jnp.int32, (tq, tq), 1)
            == tq - 1).astype(dtype)

    def rev(x):                      # J @ x, exact in x's dtype
        return jax.lax.dot_general(
            flip, x, (((1,), (0,)), ((), ())),
            precision=(jax.lax.Precision.HIGHEST if x.dtype == jnp.float32
                       else None),
            preferred_element_type=jnp.float32).astype(x.dtype)

    shift = (base - i0 % w + w) % w
    gr = pltpu.roll(rev(g).astype(jnp.float32), shift, 1,
                    stride=1, stride_axis=0).astype(dtype)
    # dqv = gP @ R = J (gr @ R);  dR += gP^T qv = gr^T (J qv)
    dqv = jax.lax.dot_general(gr, r_ref[0], (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    dqv_ref[0, 0] = rev(dqv.astype(dqv_ref.dtype))
    dr_ref[0] += jax.lax.dot_general(gr, rev(qv), (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)


def _layout(qv, r, roll: int):
    """qv (B, Sq, H, D) -> (B, H, Sq, D); r (Sk, H, D) -> (H, W, D), rows
    past sk zero, then rolled down by ``roll`` rows (cyclically)."""
    sq, sk = qv.shape[1], r.shape[0]
    w = _tiles(sq, sk)[2]
    rt = jnp.pad(r.transpose(1, 0, 2), ((0, 0), (0, w - sk), (0, 0)))
    return qv.transpose(0, 2, 1, 3), jnp.roll(rt, roll, axis=1)


def _fwd_call(qv, r, interpret: bool):
    b, sq, h, d = qv.shape
    sk = r.shape[0]
    tq, nq, w = _tiles(sq, sk)
    base, rest = _split((1 - sq) % w)
    qt, rt = _layout(qv, r, rest)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, base=base, tq=tq, w=w),
        name="xl_rel_bd",
        grid=(h, b, nq),
        in_specs=[pl.BlockSpec((1, 1, tq, d), lambda h, b, i: (b, h, i, 0)),
                  pl.BlockSpec((1, w, d), lambda h, b, i: (h, 0, 0))],
        out_specs=pl.BlockSpec((1, 1, tq, w), lambda h, b, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, sk), qt.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(qt, rt)


def _bwd_call(g, qv, r, interpret: bool):
    b, sq, h, d = qv.shape
    sk = r.shape[0]
    tq, nq, w = _tiles(sq, sk)
    base, rest = _split((sq - tq) % w)
    qt, rt = _layout(qv, r, -rest)
    dqt, drt = pl.pallas_call(
        functools.partial(_bwd_kernel, sq=sq, sk=sk, base=base, tq=tq, w=w),
        name="xl_rel_bd_bwd",
        grid=(h, b, nq),
        in_specs=[pl.BlockSpec((1, 1, tq, w), lambda h, b, i: (b, h, i, 0)),
                  pl.BlockSpec((1, 1, tq, d), lambda h, b, i: (b, h, i, 0)),
                  pl.BlockSpec((1, w, d), lambda h, b, i: (h, 0, 0))],
        out_specs=[pl.BlockSpec((1, 1, tq, d), lambda h, b, i: (b, h, i, 0)),
                   pl.BlockSpec((1, w, d), lambda h, b, i: (h, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(qt.shape, qt.dtype),
                   jax.ShapeDtypeStruct((h, w, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(g, qt, rt)
    dr = jnp.roll(drt, rest, axis=1)[:, :sk].transpose(1, 0, 2)
    return dqt.transpose(0, 2, 1, 3), dr.astype(r.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _xl_rel_bd(qv, r, interpret):
    return _fwd_call(qv, r, interpret)


def _xl_rel_bd_fwd(qv, r, interpret):
    return _xl_rel_bd(qv, r, interpret), (qv, r)


def _xl_rel_bd_bwd(interpret, res, g):
    qv, r = res
    return _bwd_call(g.astype(qv.dtype), qv, r, interpret)


_xl_rel_bd.defvjp(_xl_rel_bd_fwd, _xl_rel_bd_bwd)


def xl_rel_bd(qv: jax.Array, r: jax.Array, *,
              interpret: bool = False) -> jax.Array:
    """The shifted BD term, (B, H, Sq, Sk) in qv's dtype, accumulated in f32.

    qv (B, Sq, H, D) is the query plus the position bias; r (Sk, H, D) the
    projected sinusoids of distances sk - 1 .. 0; any D and any sq <= sk.
    Exact on the causal-valid region ``j <= (sk - sq) + i``, finite
    elsewhere; differentiable (Pallas forward and backward).
    """
    if qv.shape[1] > r.shape[0]:
        raise ValueError(f"more queries than keys: {qv.shape}, {r.shape}")
    return _xl_rel_bd(qv, r, interpret)
