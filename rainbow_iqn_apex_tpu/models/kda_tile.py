"""The in-chunk preparation of `kimi_linear.kda_chunked` as one TPU kernel.

`kda_chunked`'s plain preparation (`kimi_linear._prep_plain`) is the
definition; this is a second execution of it.  One grid step holds the
(sequence, chunk) tile of a few heads in VMEM: q, k, v, g [C, d] a head,
beta and the segment ids.  The cumulative decay, the decayed operands, both
pair products, the masks and the unit-lower-triangular solve never leave the
chip; what goes back to HBM is what the chunk scan reads, already laid out
chunk-major ([N, B, H, C, .]).

Arithmetic, as in the plain path: pair products against earlier sub-blocks
on `dtype` operands with float32 accumulation; everything else float32: the
cumulative sum (a triangular product of the three bfloat16 parts of g, which
is exact), the exponents (none positive), the pairs inside a sub-block, and
the triangular system (its inverse by forward substitution, applied as a
float32 product).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HI = jax.lax.Precision.HIGHEST
LANES = 128
HEADS = 8  # heads a grid step (a sublane tile of the [T, H, d] operands)


def takes(dk: int, dv: int, c: int, block: int) -> bool:
    """Shapes the kernel is written for; any other takes the plain path."""
    return (dk % LANES == 0 and dv % LANES == 0 and block == 8
            and c % block == 0)


def _tri_product(tri, x):
    """tri [C, C] (0/1, so exact in bfloat16) times x [C, d] in float32:
    the three bfloat16 parts of x, each an exact product, accumulated in
    float32."""
    out, rest = None, x
    for _ in range(3):
        part = rest.astype(jnp.bfloat16)
        rest = rest - part.astype(jnp.float32)
        term = jnp.dot(tri, part, preferred_element_type=jnp.float32)
        out = term if out is None else out + term
    return out


def _iota(shape, axis: int):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _lower(c: int, transpose: bool = False):
    row, col = _iota((c, c), 0), _iota((c, c), 1)
    return ((row <= col) if transpose else (col <= row)).astype(jnp.bfloat16)


@jax.custom_vjp
def _cumsum_rows(g):
    """Cumulative sum down the rows of g [C, d], float32."""
    return _tri_product(_lower(g.shape[0]), g)


# the cotangent is a cumulative sum from the last row up, as exact: the
# casts above would round one taken through them to bfloat16
_cumsum_rows.defvjp(
    lambda g: (_cumsum_rows(g), None),
    lambda _, ct: (_tri_product(_lower(ct.shape[0], True), ct),))


def _dot(a, b, dims, dtype=jnp.float32):
    """A product of `dtype` operands (float32: at the highest precision)
    with float32 accumulation, contracting a's and b's `dims`."""
    return jax.lax.dot_general(
        a.astype(dtype), b.astype(dtype), (dims, ((), ())),
        precision=HI if dtype == jnp.float32 else None,
        preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _pair_product(x, y, dtype):
    """x [m, d] times y [n, d] transposed, as `kimi_linear._mm` multiplies:
    `dtype` operands, float32 accumulation."""
    return _dot(x, y, ((1,), (1,)), dtype)


# the cotangents are products of `dtype` operands too, as the compiler makes
# them of the plain path's (a float32 cotangent against a bfloat16 operand
# would otherwise run as a float32 product of several passes here)
_pair_product.defvjp(
    lambda x, y, dtype: (_pair_product(x, y, dtype), (x, y)),
    lambda dtype, saved, ct: (_dot(ct, saved[1], ((1,), (0,)), dtype),
                              _dot(ct, saved[0], ((0,), (0,)), dtype)))


def _substitute(a, x, block: int):
    """Forward substitution, column by column: x [C, .] becomes (1 + a)^-1 x
    for a [C, C] strictly lower triangular, float32, both held as bands of
    `block` rows (a register each where x is 128 wide)."""
    bands = range(0, a.shape[0], block)
    x = [x[i0:i0 + block] for i0 in bands]
    a = [a[i0:i0 + block] for i0 in bands]
    for j in range(len(x) * block - 1):
        at, r = divmod(j, block)
        row = x[at][r:r + 1]
        for b in range(at, len(x)):
            x[b] = x[b] - a[b][:, j:j + 1] * row  # a is zero in rows <= j
    return jnp.concatenate(x, axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _solve(a, rv, rk, block: int):
    """(1 + a) [u | wk] = [rv | rk], a strictly lower triangular."""
    return _substitute(a, rv, block), _substitute(a, rk, block)


def _solve_fwd(a, rv, rk, block):
    # differentiated, the inverse itself is wanted: the cotangents are
    # products with it, and so then is the solution
    eye = (_iota(a.shape, 0) == _iota(a.shape, 1)).astype(jnp.float32)
    t = _substitute(a, eye, block)
    u, wk = _dot(t, rv, ((1,), (0,))), _dot(t, rk, ((1,), (0,)))
    return (u, wk), (t, u, wk)


def _solve_bwd(block, saved, cot):
    t, u, wk = saved
    drv, drk = (_dot(t, d, ((0,), (0,))) for d in cot)  # t^T d
    da = -(_dot(drv, u, ((1,), (1,))) + _dot(drk, wk, ((1,), (1,))))
    return da, drv, drk


_solve.defvjp(_solve_fwd, _solve_bwd)


def tile(q, k, v, g, bcol, seg_col, seg_row, seg_in, block: int, dtype):
    """One head of one (sequence, chunk): q, k, g [C, dk], v [C, dv] float32,
    bcol [C, 1] beta, seg_col [C, 1], seg_row [1, C] the chunk's segment
    ids, seg_in [1, 1] the id the incoming state belongs to.  Returns u
    [C, dv], wk, qg [C, dk], a_qk [C, C], k_end [C, dk], s_keep [1, dk],
    all float32."""
    c = k.shape[0]
    m0 = (seg_col == seg_in).astype(jnp.float32)  # [C, 1]
    m_end = (seg_col == seg_col[c - 1:c]).astype(jnp.float32)
    g_cum = _cumsum_rows(g)
    cols = _iota((block, c), 1)
    later = [_iota((block, k.shape[1]), 0) >= i for i in range(block)]

    a_bands, aqk_bands = [], []
    for i0 in range(0, c, block):
        band = slice(i0, i0 + block)
        g_b, k_b, q_b = g_cum[band], k[band], q[band]
        rows = i0 + _iota((block, c), 0)
        kk = jnp.zeros((block, c), jnp.float32)
        qk = jnp.zeros((block, c), jnp.float32)
        if i0:
            # earlier sub-blocks, about this one's first row: both exponents
            # are non-positive there (the clip only touches masked columns)
            ref = g_b[0:1]
            e = jnp.exp(g_b - ref)
            x2 = jnp.concatenate([k_b * e, q_b * e], axis=0)
            yc = k * jnp.exp(jnp.minimum(ref - g_cum, 0.0))
            off = _pair_product(x2, yc, dtype)  # [2 block, C]
            kk, qk = off[:block], off[block:]
        for i in range(block):
            # inside the sub-block, pair by pair: column i0 + i
            # (a select, not a clip: where two steps' sums are equal a clip
            # at 0 would pass half the cotangent)
            m = k_b[i:i + 1] * jnp.exp(
                jnp.where(later[i], g_b - g_b[i:i + 1], 0.0))
            hit = cols == i0 + i
            kk = jnp.where(hit, jnp.sum(k_b * m, axis=-1, keepdims=True), kk)
            qk = jnp.where(hit, jnp.sum(q_b * m, axis=-1, keepdims=True), qk)
        ok = seg_col[band] == seg_row  # [block, C] (a mask takes no slice)
        a_bands.append(bcol[band] * jnp.where(
            ok & (cols < rows), kk, 0.0))
        aqk_bands.append(jnp.where(ok & (cols <= rows), qk, 0.0))

    decay = jnp.exp(g_cum)
    kg, qg = k * decay * m0, q * decay * m0
    u, wk = _solve(jnp.concatenate(a_bands, axis=0), bcol * v, bcol * kg,
                   block)
    g_last = g_cum[c - 1:c]
    k_end = k * jnp.exp(g_last - g_cum) * m_end
    s_keep = jnp.exp(g_last) * m0[c - 1:c]
    return u, wk, qg, jnp.concatenate(aqk_bands, axis=0), k_end, s_keep


def _head(refs, h):
    """Head h's operands out of a grid step's blocks (h is the loop's)."""
    q_ref, k_ref, v_ref, g_ref, beta_ref, segc_ref, segr_ref, segin_ref = refs
    one = lambda ref: ref[0, :, pl.ds(h, 1), :][:, 0, :]  # noqa: E731
    beta = beta_ref[0, 0]  # [C, heads]
    lane = _iota(beta.shape, 1)
    bcol = jnp.sum(jnp.where(lane == h, beta, 0.0), axis=-1, keepdims=True)
    return ((one(q_ref), one(k_ref), one(v_ref), one(g_ref), bcol),
            (segc_ref[0], segr_ref[0, 0], segin_ref[0, 0]))


# A grid step's heads run as a loop: unrolled, the kernels are 3 to 8% faster
# (chip runs, PR 28) and their code three to four times larger (1.2 and 2.6
# MB an instance against 0.4 and 0.65; the segment holds 20 and 4 of them),
# which every compile and every load of the cached executable would pay.
def _tile_kernel(*refs, heads: int, block: int, dtype):
    ins, outs = refs[:8], refs[8:]

    def one(h, carry):
        x, seg = _head(ins, h)
        for ref, y in zip(outs, tile(*x, *seg, block, dtype)):
            ref[0, 0, h] = y.astype(ref.dtype)
        return carry

    jax.lax.fori_loop(0, heads, one, 0)


def _tile_vjp_kernel(*refs, heads: int, block: int, dtype):
    """The cotangents of q, k, v, g, beta from those of `tile`'s results:
    the tile is computed again and its own VJP, as JAX traces it, runs on
    the values in VMEM."""
    ins, cots, outs = refs[:8], refs[8:14], refs[14:]
    lane = _iota(outs[4].shape[2:], 1)

    def one(h, dbeta):
        x, seg = _head(ins, h)
        _, vjp = jax.vjp(lambda *z: tile(*z, *seg, block, dtype), *x)
        *wide, dbcol = vjp(tuple(
            ref[0, 0, h].astype(jnp.float32) for ref in cots))
        for ref, y in zip(outs, wide):
            ref[0, :, pl.ds(h, 1), :] = y[:, None, :]
        return jnp.where(lane == h, dbcol, dbeta)

    outs[4][0, 0] = jax.lax.fori_loop(
        0, heads, one, jnp.zeros(lane.shape, jnp.float32))


def _call(q, k, v, g, beta, seg, cots, c: int, block: int, dtype):
    """A kernel over the (sequence, chunk, head group) grid.  `cots` None:
    the preparation, returning its six results; else their cotangents, and
    the cotangents of q, k, v, g, beta come back."""
    b, t, h, dk = q.shape
    dv, n = v.shape[-1], t // c
    hb = h if h % HEADS else HEADS
    # beta by head group, so that a block's last dimension is a whole one
    beta_g = jnp.swapaxes(beta.reshape(b, t, h // hb, hb), 1, 2)
    seg = seg.astype(jnp.int32)
    seg_in = jnp.concatenate(
        [jnp.zeros((b, 1), jnp.int32), seg.reshape(b, n, c)[:, :-1, -1]],
        axis=1)
    wide = lambda d: pl.BlockSpec(  # noqa: E731
        (1, c, hb, d), lambda i, j, m: (i, j, m, 0))
    beta_spec = pl.BlockSpec((1, 1, c, hb), lambda i, j, m: (i, m, j, 0))
    major = lambda *tail: pl.BlockSpec(  # noqa: E731
        (1, 1, hb) + tail, lambda i, j, m: (j, i, m) + (0,) * len(tail))
    in_specs = [wide(dk), wide(dk), wide(dv), wide(dk), beta_spec,
                pl.BlockSpec((1, c, 1), lambda i, j, m: (i, j, 0)),
                pl.BlockSpec((1, 1, 1, c), lambda i, j, m: (i, j, 0, 0)),
                pl.BlockSpec((1, 1, 1, 1), lambda i, j, m: (i, j, 0, 0))]
    args = [q, k, v, g, beta_g, seg.reshape(b, t, 1),
            seg.reshape(b, n, 1, c), seg_in.reshape(b, n, 1, 1)]
    results = [major(c, dv), major(c, dk), major(c, dk), major(c, c),
               major(c, dk), major(1, dk)]
    shape = lambda dt, *tail: jax.ShapeDtypeStruct((n, b, h) + tail, dt)  # noqa: E731
    if cots is None:
        kernel, name = _tile_kernel, "kda_tile"
        out_specs = results
        out_shape = [shape(jnp.float32, c, dv), shape(dtype, c, dk),
                     shape(dtype, c, dk), shape(dtype, c, c),
                     shape(dtype, c, dk), shape(jnp.float32, 1, dk)]
    else:
        kernel, name = _tile_vjp_kernel, "kda_tile_vjp"
        in_specs += results
        args += [*cots[:5], cots[5][..., None, :]]
        out_specs = [wide(dk), wide(dk), wide(dv), wide(dk), beta_spec]
        out_shape = [jax.ShapeDtypeStruct(z.shape, jnp.float32)
                     for z in (q, k, v, g, beta_g)]
    return pl.pallas_call(
        functools.partial(kernel, heads=hb, block=block, dtype=dtype),
        grid=(b, n, h // hb), in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        name=name,  # not the scope's: `scope_path` would read it as one more
    )(*args)


# Both entry points are jitted: a model calls them once a layer and pass
# (24 times in the fused segment), and a kernel's trace and lowering (a
# second of Python each) is then done once a shape, not once a call.
@functools.partial(jax.jit, static_argnames=("c", "block", "dtype"))
def prepare(q, k, v, g, beta, seg, c: int, block: int, dtype):
    """q, k, g [B, T, H, dk], v [B, T, H, dv], beta [B, T, H], seg [B, T]
    with T a multiple of the chunk c.  Returns what `kimi_linear._prep_plain`
    does: the chunk scan's operands, chunk-major."""
    u, wk, qg, a_qk, k_end, s_keep = _call(
        q, k, v, g, beta, seg, None, c, block, dtype)
    return u, wk, qg, a_qk, k_end, s_keep[..., 0, :]


@functools.partial(jax.jit, static_argnames=("c", "block", "dtype"))
def prepare_vjp(q, k, v, g, beta, seg, cots, c: int, block: int, dtype):
    """Cotangents of q, k, v, g, beta from those of `prepare`'s results."""
    dq, dk, dv, dg, dbeta_g = _call(
        q, k, v, g, beta, seg, cots, c, block, dtype)
    return dq, dk, dv, dg, jnp.swapaxes(dbeta_g, 1, 2).reshape(beta.shape)
