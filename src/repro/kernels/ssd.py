"""Mamba2 SSD chunked-scan Pallas kernels: a forward and its backward.

Both mirror ``repro.nn.mamba2.ssd_chunked``: x (B,S,H,P), dt (B,S,H), a
(H,), b/c (B,S,N) shared by every head (one group), state (B,H,P,N).  The
kernels take x, y and dt as xᵀ (B,H,P,S), yᵀ and dtᵀ (B,H,S), what XLA's
TPU layouts of them already are (the sequence minor, P = 64 and H = 64
being under a lane width), so the transposes around them are free; each
head's chunk is a dense (P, chunk) tile and a (1, chunk) row of dt.  The
grid is (B, S/chunk, H/hb): the chunks in order (in reverse in the
backward) and, inside each chunk, the heads in groups of ``hb``.  The
(P, N) states of all heads
stay in VMEM across chunks, in the resident final-state block (its
cotangent's in the backward), and ``C Bᵀ`` is computed once per program
for all of its heads.  Each head's segsum decay is built in VMEM: nothing
of shape (chunk, chunk, H) reaches HBM.

:func:`ssd` is differentiable through ``jax.custom_vjp``, and its precision
follows its use:

* undifferentiated (inference, prefill) it runs the forward alone, every
  contraction at full float32 (``Precision.HIGHEST``: Mosaic's default
  contracts float32 in one bfloat16 pass);
* under ``jax.grad`` the forward and the backward contract in one bfloat16
  pass with float32 accumulation, what XLA compiles the float32 einsums of
  ``ssd_chunked`` to, and the forward also writes the state entering each
  chunk, (B, H, S/chunk, P, N) float32, as the residual from which the
  backward recomputes each chunk.  The backward carries the state's
  cotangent across chunks in reverse, sums ``dB`` and ``dC`` over the heads
  in VMEM, and writes ``da``'s per-position terms, which are summed per
  head in float32.

The decays, cumsums, states and all accumulation are float32 in both uses.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HIGHEST = jax.lax.Precision.HIGHEST
ONE_PASS = None                # the default: one bfloat16 pass, as XLA's
GRID = 1024.0
HEADS = 8                      # heads per program where H allows
VMEM_LIMIT = 64 * 2 ** 20


def _heads_per_program(h):
    """``HEADS`` where they divide ``h``, else all of them: each program
    computes ``C Bᵀ`` once for its heads."""
    return HEADS if h % HEADS == 0 else h


def _dot(a, b, precision, *, ta=False, tb=False):
    """``a @ b`` with either operand transposed, accumulated in float32."""
    dims = (((0 if ta else 1,), (1 if tb else 0,)), ((), ()))
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=jnp.float32)


def _to_col(row, rows, cols):
    """(1, CL) row -> (CL, 1) column (Mosaic has no such relayout)."""
    return jnp.sum(jnp.where(rows == cols, row, 0.0), axis=1, keepdims=True)


def _to_row(col, rows, cols):
    return jnp.sum(jnp.where(rows == cols, col, 0.0), axis=0, keepdims=True)


def _cumsum(v, rows, cols):
    """Inclusive cumsum of a (1, CL) row as a (1, CL) row, a (CL, 1) column
    and the (1, 1) total.  Mosaic has no cumsum, so all three are masked
    reductions."""
    col = jnp.sum(jnp.where(cols <= rows, v, 0.0), axis=1, keepdims=True)
    return _to_row(col, rows, cols), col, jnp.sum(v, axis=1, keepdims=True)


def _segsum(lda, rows, cols):
    """From a chunk's log-decays ``lda`` (1, CL), all <= 0: their cumsum
    ``ca`` as a (1, CL) row, its (1, 1) total, and the decay
    ``exp(ca_t - ca_s)`` for s <= t (0 above the diagonal) as (CL, CL).

    The segsum is a difference of cumsums that reach hundreds over a chunk
    of 256, which float32 rounds to ~1e-4: each log-decay is split into a
    part on a 2**-10 grid, whose cumsums are exact while below 2**14, and a
    remainder under 2**-11, and the two are differenced apart."""
    hi = jnp.floor(lda * GRID + 0.5) / GRID
    (hr, hc, ht), (lr, lc, lt) = (_cumsum(v, rows, cols)
                                  for v in (hi, lda - hi))
    # above the diagonal the exponent is positive and may overflow: the
    # select never takes it
    decay = jnp.where(rows >= cols, jnp.exp((hc - hr) + (lc - lr)), 0.0)
    return hr + lr, ht + lt, decay


def _iota(n):
    return (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0),
            jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))


def _fwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, s0_ref, y_ref, sout_ref,
                *res_ref, hb: int, precision):
    ci, g = pl.program_id(1), pl.program_id(2)
    heads = pl.ds(g * hb, hb)

    @pl.when(ci == 0)
    def _():
        sout_ref[0, heads] = s0_ref[0, heads].astype(jnp.float32)

    bb = b_ref[0].astype(jnp.float32)          # (CL, N)
    cc = c_ref[0].astype(jnp.float32)          # (CL, N)
    rows, cols = _iota(bb.shape[0])
    cb = _dot(cc, bb, precision, tb=True)      # (CLt, CLs), all heads'

    def head(j, carry):
        h = g * hb + j
        xt = x_ref[0, j].astype(jnp.float32)           # (P, CL)
        dt = dt_ref[0, pl.ds(j, 1), :]                 # (1, CL)
        ca, ca_tot, decay = _segsum(dt * a_ref[h], rows, cols)
        st = sout_ref[0, h]                            # (P, N) entering
        if res_ref:
            res_ref[0][0, j, 0] = st
        y = _dot(xt, cb * decay * dt, precision, tb=True)
        y = y + jnp.exp(ca) * _dot(st, cc, precision, tb=True)
        w = jnp.exp(ca_tot - ca) * dt                  # (1, CL)
        sout_ref[0, h] = jnp.exp(ca_tot) * st + _dot(xt * w, bb, precision)
        y_ref[0, j] = y.astype(y_ref.dtype)
        return carry

    jax.lax.fori_loop(0, hb, head, 0)


def _bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, st_ref, dy_ref, dsf_ref,
                dx_ref, ddt_ref, dda_ref, db_ref, dc_ref, ds_ref,
                dcb_acc, db_acc, dc_acc, *, hb: int, precision):
    ci, g = pl.program_id(1), pl.program_id(2)
    heads = pl.ds(g * hb, hb)

    @pl.when(ci == 0)
    def _():
        ds_ref[0, heads] = dsf_ref[0, heads].astype(jnp.float32)

    @pl.when(g == 0)
    def _():
        dcb_acc[...] = jnp.zeros_like(dcb_acc)
        db_acc[...] = jnp.zeros_like(db_acc)
        dc_acc[...] = jnp.zeros_like(dc_acc)

    bb = b_ref[0].astype(jnp.float32)
    cc = c_ref[0].astype(jnp.float32)
    rows, cols = _iota(bb.shape[0])
    cb = _dot(cc, bb, precision, tb=True)

    def head(j, carry):
        h = g * hb + j
        a = a_ref[h]
        xt = x_ref[0, j].astype(jnp.float32)           # (P, CL)
        dyt = dy_ref[0, j].astype(jnp.float32)
        dt = dt_ref[0, pl.ds(j, 1), :]                 # (1, CL)
        h0 = st_ref[0, j, 0]                           # (P, N) entering
        dh1 = ds_ref[0, h]                             # (P, N) leaving
        ca, ca_tot, decay = _segsum(dt * a, rows, cols)
        e = jnp.exp(ca)                                # (1, CL)
        e_tot = jnp.exp(ca_tot)                        # (1, 1)
        v = jnp.exp(ca_tot - ca)                       # (1, CL)
        w = v * dt

        # intra-chunk: y += M x, M = cb * decay * dt_s
        ld = decay * dt
        m = cb * ld
        dm = _dot(dyt, xt, precision, ta=True)         # (CLt, CLs)
        dcb = dm * ld
        dcb_acc[...] += dcb
        gm = dcb * cb                                  # dM * M
        q = jnp.sum(dm * cb * decay, axis=0, keepdims=True)   # (1, CL)
        # inter-chunk: y += e * C h0ᵀ; h1 = e_tot h0 + xᵀ (w * B)
        bdh = _dot(dh1, bb, precision, tb=True)        # (P, CL)
        dw = jnp.sum(xt * bdh, axis=0, keepdims=True)  # (1, CL)
        ey = dyt * e                                   # (P, CL)
        dx = _dot(dyt, m, precision) + w * bdh
        dc_acc[...] += _dot(ey, h0, precision, ta=True)
        db_acc[...] += _dot(xt * w, dh1, precision, ta=True)
        ds_ref[0, h] = e_tot * dh1 + _dot(ey, cc, precision)

        # the log-decays: ca_t enters M's rows, ca_s its columns, e, w and
        # e_tot; dlda_s = sum_{t >= s} dca_t + dca_tot
        ch0 = _dot(h0, cc, precision, tb=True)         # (P, CL)
        dca = (jnp.sum(gm, axis=1, keepdims=True)
               - _to_col(jnp.sum(gm, axis=0, keepdims=True)
                         - jnp.sum(ey * ch0, axis=0, keepdims=True)
                         + dw * w, rows, cols))
        dca_tot = (e_tot * jnp.sum(dh1 * h0, keepdims=True)
                   + jnp.sum(dw * w, keepdims=True))
        dlda = jnp.sum(jnp.where(rows >= cols, dca, 0.0), axis=0,
                       keepdims=True) + dca_tot       # (1, CL)
        ddt_ref[0, pl.ds(j, 1), :] = q + dw * v + a * dlda
        dda_ref[0, pl.ds(j, 1), :] = dt * dlda
        dx_ref[0, j] = dx.astype(dx_ref.dtype)
        return carry

    jax.lax.fori_loop(0, hb, head, 0)

    @pl.when(g == pl.num_programs(2) - 1)
    def _():
        dcb = dcb_acc[...]
        db_ref[0] = (db_acc[...] + _dot(dcb, cc, precision, ta=True)
                     ).astype(db_ref.dtype)
        dc_ref[0] = (dc_acc[...] + _dot(dcb, bb, precision)
                     ).astype(dc_ref.dtype)


def _specs(shape, n, chunk, hb, rev=False):
    """Block specs of xᵀ/yᵀ, dtᵀ, b/c, the per-chunk states and the whole
    (B, H, P, N) state, on the grid (B, S/chunk, H/hb)."""
    bsz, h, p, s = shape
    nc = s // chunk
    c_ = (lambda ci: nc - 1 - ci) if rev else (lambda ci: ci)
    return (
        pl.BlockSpec((1, hb, p, chunk), lambda bi, ci, g: (bi, g, 0, c_(ci))),
        pl.BlockSpec((1, hb, chunk), lambda bi, ci, g: (bi, g, c_(ci))),
        pl.BlockSpec((1, chunk, n), lambda bi, ci, g: (bi, c_(ci), 0)),
        pl.BlockSpec((1, hb, 1, p, n),
                     lambda bi, ci, g: (bi, g, c_(ci), 0, 0)),
        pl.BlockSpec((1, h, p, n), lambda bi, ci, g: (bi, 0, 0, 0)),
    )


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary",) * 3, vmem_limit_bytes=VMEM_LIMIT)


def _forward(x, dt, a, b, c, s0, chunk, interpret, precision, residual):
    bsz, h, p, s = x.shape
    n = b.shape[-1]
    hb = _heads_per_program(h)
    xs, dts, bcs, sts, whole = _specs(x.shape, n, chunk, hb)
    out_specs = [xs, whole]
    out_shape = [jax.ShapeDtypeStruct(x.shape, jnp.float32),
                 jax.ShapeDtypeStruct((bsz, h, p, n), jnp.float32)]
    if residual:
        out_specs.append(sts)
        out_shape.append(jax.ShapeDtypeStruct((bsz, h, s // chunk, p, n),
                                              jnp.float32))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, hb=hb, precision=precision),
        grid=(bsz, s // chunk, h // hb),
        in_specs=[xs, dts, pl.BlockSpec(memory_space=pltpu.SMEM), bcs, bcs,
                  whole],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=_params(),
        interpret=interpret,
        name="ssd",
    )(x, dt.astype(jnp.float32), a.astype(jnp.float32), b, c, s0)


def _backward(x, dt, a, b, c, states, dy, dsf, chunk, interpret):
    bsz, h, p, s = x.shape
    n = b.shape[-1]
    hb = _heads_per_program(h)
    xs, dts, bcs, sts, whole = _specs(x.shape, n, chunk, hb, rev=True)
    f32 = jnp.float32
    per_head = jax.ShapeDtypeStruct((bsz, h, s), f32)
    dx, ddt, dda, db, dc, ds0 = pl.pallas_call(
        functools.partial(_bwd_kernel, hb=hb, precision=ONE_PASS),
        grid=(bsz, s // chunk, h // hb),
        in_specs=[xs, dts, pl.BlockSpec(memory_space=pltpu.SMEM), bcs, bcs,
                  sts, xs, whole],
        out_specs=[xs, dts, dts, bcs, bcs, whole],
        out_shape=[jax.ShapeDtypeStruct(x.shape, f32), per_head, per_head,
                   jax.ShapeDtypeStruct(b.shape, f32),
                   jax.ShapeDtypeStruct(c.shape, f32),
                   jax.ShapeDtypeStruct((bsz, h, p, n), f32)],
        scratch_shapes=[pltpu.VMEM((chunk, chunk), f32),
                        pltpu.VMEM((chunk, n), f32),
                        pltpu.VMEM((chunk, n), f32)],
        compiler_params=_params(),
        interpret=interpret,
        name="ssd_bwd",
    )(x, dt.astype(f32), a.astype(f32), b, c, states, dy, dsf)
    return dx, ddt, jnp.sum(dda, axis=(0, 2)), db, dc, ds0


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _ssd(x, dt, a, b, c, initial_state, chunk, interpret):
    return tuple(_forward(x, dt, a, b, c, initial_state, chunk, interpret,
                          HIGHEST, residual=False))


def _ssd_fwd(x, dt, a, b, c, initial_state, chunk, interpret):
    y, sout, states = _forward(x, dt, a, b, c, initial_state, chunk,
                               interpret, ONE_PASS, residual=True)
    return (y, sout), (x, dt, a, b, c, initial_state, states)


def _ssd_bwd(chunk, interpret, res, cts):
    x, dt, a, b, c, s0, states = res
    dy, dsf = cts
    grads = _backward(x, dt, a, b, c, states, dy, dsf, chunk, interpret)
    return tuple(g.astype(t.dtype) for g, t in zip(grads, (x, dt, a, b, c,
                                                           s0)))


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def tiles(s, chunk):
    """Whether a sequence of ``s`` steps splits into chunks the kernels'
    blocks can take: a chunk is a block's minor dim."""
    return s % chunk == 0 and (chunk == s or chunk % 128 == 0)


def ssd(x, dt, a, b, c, initial_state, *, chunk: int = 128,
        interpret: bool = False):
    """x: (B,S,H,P); dt: (B,S,H); a: (H,); b/c: (B,S,N); state: (B,H,P,N).
    Returns (y (B,S,H,P), final state (B,H,P,N)), both float32."""
    chunk = min(chunk, x.shape[1])
    assert x.shape[1] % chunk == 0
    yt, sout = _ssd(jnp.transpose(x, (0, 2, 3, 1)), jnp.swapaxes(dt, 1, 2),
                    a, b, c, initial_state, chunk, interpret)
    return jnp.transpose(yt, (0, 3, 1, 2)), sout
