"""Mamba2 chunked SSD scan (Pallas TPU): forward and backward kernels
under one ``jax.custom_vjp``.

The inputs are read where the Mamba2 block leaves them: ``x`` and ``y`` as
[B, L, H*P], ``dt`` as [B, L, H], ``B`` and ``C`` as [B, L, N].  A block is
one chunk of Q rows with every head; the grid is (batch, chunk), the chunk
axis sequential.  Per chunk the kernel forms the inclusive cumulative
decay of all heads at once (a log-step scan over rows), ``C B^T`` once
(ngroups 1), and then walks the heads in lane groups of G heads, G*P lanes
(128 at the published head dim of 64): per head the within-chunk decay
tile, masked to -inf above the diagonal before ``exp``, in sub-tiles of up
to 128 x 128 of which the wholly masked ones above the diagonal are
skipped; per group the read-out of the chunk state and its update.  The
state lives transposed, [N, H*P] in float32 VMEM scratch, so every
per-head factor lies along lanes; per-head columns are lane gathers.

The backward runs the chunks in reverse with the state's cotangent in
VMEM, from the chunk-start states the forward saved, and sums ``dB`` and
``dC`` over heads in VMEM.  Matmul operands are in ``x.dtype`` with
float32 accumulation; the decay, ``exp`` and the state are float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.scopes import SSD_SCOPE

F32 = jnp.float32
LANES = 128
VMEM_LIMIT = 96 * 2 ** 20   # of the v5e's 128 MiB


def lane_group(H: int, P: int) -> int:
    """Heads per lane group: the most heads, a divisor of H, whose P
    columns together span at most 128 lanes (one head where P >= 128)."""
    return max(g for g in range(1, H + 1)
               if H % g == 0 and g * P <= max(P, LANES))


def sub_tile(Q: int) -> int:
    """Side of the within-chunk sub-tiles: 128 where it divides Q."""
    return LANES if Q % LANES == 0 else Q


def _dot(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=F32)


def _dot_nt(a, b):   # a [m, k] . b [n, k]^T
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=F32)


def _dot_tn(a, b):   # a [k, m]^T . b [k, n]
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=F32)


def _scan_rows(v, reverse=False):
    """Inclusive prefix (or suffix) sum over rows, in log2(rows) steps."""
    n = v.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
    k = 1
    while k < n:
        if reverse:
            v = v + jnp.where(row < n - k, pltpu.roll(v, n - k, 0), 0.0)
        else:
            v = v + jnp.where(row >= k, pltpu.roll(v, k, 0), 0.0)
        k *= 2
    return v


def _pad_lanes(v):
    pad = -v.shape[1] % LANES
    return jnp.pad(v, ((0, 0), (0, pad))) if pad else v


def _heads_of(rows, W, P, first=0):
    """The head of each lane of a [rows, W] block of head columns."""
    return first + jax.lax.broadcasted_iota(jnp.int32, (rows, W), 1) // P


def _lane_sums(parts):
    """Row sums [R, 1] of the arrays in ``parts``; those of one shape are
    added first and reduced over lanes once."""
    by_shape = {}
    for a in parts:
        by_shape[a.shape] = by_shape[a.shape] + a if a.shape in by_shape else a
    return sum(jnp.sum(a, axis=1, keepdims=True) for a in by_shape.values())


def _gather(table, idx):
    """table [R, 128*k] gathered along lanes by idx [R, n]."""
    return jnp.take_along_axis(table, idx, axis=1)


class _Chunk:
    """What one chunk's heads share: the cumulative decay and its factors
    (heads on lanes, padded to 128), the per-head rows in scratch, and the
    sub-tiles of C B^T."""

    def __init__(self, dt_ref, a_ref, b_ref, c_ref, cumT, *, T):
        dt = dt_ref[...].astype(F32)                      # [Q, H]
        Q = dt.shape[0]
        cum = _scan_rows(dt * a_ref[...])                 # [Q, H], <= 0
        total = cum[Q - 1:Q, :]                           # [1, H]
        for k in range(Q // T):      # per sub-tile, rows read at lane 0
            cumT[k] = cum[k * T:(k + 1) * T].T
        self.dt = dt
        self.cum_p = _pad_lanes(cum)
        self.dt_p = _pad_lanes(dt)
        self.e_p = _pad_lanes(jnp.exp(cum))               # exp(cum_t)
        self.e2_p = _pad_lanes(jnp.exp(total - cum))      # exp(total - cum_s)
        self.dec_p = jnp.broadcast_to(                    # [8, 128]
            _pad_lanes(jnp.exp(total)), (8, LANES))
        self.cumT = cumT
        self.Bm, self.Cm = b_ref[...], c_ref[...]
        self.T, self.nb = T, Q // T
        self.cb = {(i, k): _dot_nt(self.Cm[i * T:(i + 1) * T],
                                   self.Bm[k * T:(k + 1) * T])
                   for i in range(self.nb) for k in range(i + 1)}
        r = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
        s = jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
        self.tri = r >= s

    def head(self, h):
        """Head h's cumulative decay as lane-broadcast rows [Q, T], and as
        rows [1, T], one per sub-tile."""
        Q, T = self.cum_p.shape[0], self.T
        ccol = _gather(self.cum_p, jnp.full((Q, LANES), h, jnp.int32))
        return (ccol[:, :T],
                [self.cumT[k, pl.ds(h, 1), :] for k in range(self.nb)])

    def tile(self, ccol, crow, i, k):
        """exp(cum_t - cum_s) over rows i, columns k, zero above the
        diagonal (masked before exp)."""
        T = self.T
        seg = ccol[i * T:(i + 1) * T] - crow[k]
        if i == k:
            seg = jnp.where(self.tri, seg, -jnp.inf)
        return jnp.exp(seg)


def _fwd_kernel(*refs, T, G, P, has_init, save_states):
    it = iter(refs)
    x_ref, dt_ref, a_ref, b_ref, c_ref = (next(it) for _ in range(5))
    s0_ref = next(it) if has_init else None
    y_ref, sf_ref = next(it), next(it)
    st_ref = next(it) if save_states else None
    s_scr, cumT = next(it), next(it)
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _start():
        if has_init:
            s_scr[...] = s0_ref[...]
        else:
            s_scr[...] = jnp.zeros_like(s_scr)

    if save_states:
        st_ref[...] = s_scr[...]
    k = _Chunk(dt_ref, a_ref, b_ref, c_ref, cumT, T=T)
    Q, HP = x_ref.shape
    W = G * P
    dtype = x_ref.dtype
    head_T = _heads_of(T, W, P)

    def group(g, carry):
        off = pl.multiple_of(g * W, W)
        sg = s_scr[:, pl.ds(off, W)]                       # [N, W]
        idx = _heads_of(Q, W, P, g * G)
        xs = x_ref[:, pl.ds(off, W)].astype(F32) * _gather(k.dt_p, idx)
        xsd = xs.astype(dtype)                             # dt_s x_s
        y = _gather(k.e_p, idx) * _dot(k.Cm, sg.astype(dtype))
        ys = [y[i * T:(i + 1) * T] for i in range(k.nb)]
        for j in range(G):
            ccol, crow = k.head(g * G + j)
            for i in range(k.nb):
                acc = 0.0
                for kk in range(i + 1):
                    m = k.cb[i, kk] * k.tile(ccol, crow, i, kk)
                    acc = acc + _dot(m.astype(dtype),
                                     xsd[kk * T:(kk + 1) * T])
                ys[i] = jnp.where(head_T == j, ys[i] + acc, ys[i])
        for i in range(k.nb):
            y_ref[i * T:(i + 1) * T, pl.ds(off, W)] = ys[i].astype(
                y_ref.dtype)
        xw = (xs * _gather(k.e2_p, idx)).astype(dtype)
        dec = _gather(k.dec_p, _heads_of(8, W, P, g * G))[:1]   # [1, W]
        s_scr[:, pl.ds(off, W)] = dec * sg + _dot_tn(k.Bm, xw)
        return carry

    jax.lax.fori_loop(0, HP // W, group, 0)

    @pl.when(c == pl.num_programs(1) - 1)
    def _end():
        sf_ref[...] = s_scr[...]


def _bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, st_ref, dy_ref, dsf_ref,
                dx_ref, ddt_ref, db_ref, dc_ref, da_ref, ds0_ref,
                ds_scr, cumT, dcb_scr, dcq_scr, dbq_scr, col_scr, row_scr,
                *, T, G, P):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _start():
        ds_scr[...] = dsf_ref[...]
        da_ref[...] = jnp.zeros_like(da_ref)

    k = _Chunk(dt_ref, a_ref, b_ref, c_ref, cumT, T=T)
    Q, HP = x_ref.shape
    H = k.dt.shape[1]
    W = G * P
    dtype = x_ref.dtype
    head_T = _heads_of(T, W, P)
    lane_T = jax.lax.broadcasted_iota(jnp.int32, (T, LANES), 1)
    last = jax.lax.broadcasted_iota(jnp.int32, (T, 1), 0) == T - 1
    dcb_scr[...] = jnp.zeros_like(dcb_scr)
    dcq_scr[...] = jnp.zeros_like(dcq_scr)
    dbq_scr[...] = jnp.zeros_like(dbq_scr)
    col_scr[...] = jnp.zeros_like(col_scr)
    tiles = [(i, kk) for i in range(k.nb) for kk in range(i + 1)]
    blocks = [slice(i * T, (i + 1) * T) for i in range(k.nb)]

    def group(g, carry):
        off = pl.multiple_of(g * W, W)
        dyg = dy_ref[:, pl.ds(off, W)]                     # [Q, W]
        sg = st_ref[:, pl.ds(off, W)]                      # [N, W] state in
        dsn = ds_scr[:, pl.ds(off, W)]                     # [N, W] dstate out
        idx = _heads_of(Q, W, P, g * G)
        E, E2, DT = (_gather(t, idx) for t in (k.e_p, k.e2_p, k.dt_p))
        dec = _gather(k.dec_p, _heads_of(8, W, P, g * G))[:1]
        xf, dyf = x_ref[:, pl.ds(off, W)].astype(F32), dyg.astype(F32)
        xs = xf * DT                                       # dt_s x_s
        xsd = xs.astype(dtype)
        # read-out of the chunk-start state: y += E * (C S^T)
        Z = _dot(k.Cm, sg.astype(dtype))
        dZ = (dyf * E).astype(dtype)
        dcq_scr[...] += _dot_nt(dZ, sg.astype(dtype))
        ds_scr[:, pl.ds(off, W)] = dec * dsn + _dot_tn(k.Cm, dZ)
        # the state's update: S' = dec * S + B^T (xs * E2)
        dxw = _dot(k.Bm, dsn.astype(dtype))                # [Q, W]
        dbq_scr[...] += _dot_nt((xs * E2).astype(dtype), dsn.astype(dtype))
        dE2 = dxw * xs
        dxs = dxw * E2                                     # d xs, per lane
        u = dyf * Z * E - dE2 * E2                         # d cum_t, per lane
        tot = (jnp.sum(dsn * sg, axis=0, keepdims=True) * dec
               + jnp.sum(dE2 * E2, axis=0, keepdims=True))  # d total, [1, W]
        dxs = [dxs[r] for r in blocks]
        dcb = [0.0] * len(tiles)       # d(C B^T) of the group's heads
        dcol = [0.0] * k.nb            # d cum_t of the group's heads, by lane
        for j in range(G):
            h = g * G + j
            mine = head_T == j
            ccol, crow = k.head(h)
            dtot = jnp.sum(jnp.where(_heads_of(1, W, P) == j, tot, 0.0),
                           axis=1, keepdims=True)          # [1, 1]
            # d cum_t: this head's lanes of u and its tiles' rows, summed
            # over lanes together where the shapes agree
            dcum = [[jnp.where(mine, u[r], 0.0)] for r in blocks]
            dyj = [jnp.where(mine, dyf[r], 0.0).astype(dtype) for r in blocks]
            csum = [0.0] * k.nb        # - d cum_s from the tiles, [1, T]
            dxj = [0.0] * k.nb
            for n, (i, kk) in enumerate(tiles):
                L = k.tile(ccol, crow, i, kk)
                Gm = k.cb[i, kk] * L
                dG = _dot_nt(dyj[i], xsd[blocks[kk]])      # [T, T]
                dxj[kk] = dxj[kk] + _dot_tn(Gm.astype(dtype),
                                            dyg[blocks[i]])
                dseg = dG * Gm
                dcum[i].append(dseg)
                csum[kk] = csum[kk] + jnp.sum(dseg, axis=0, keepdims=True)
                dcb[n] = dcb[n] + dG * L
            for i in range(k.nb):
                col = _lane_sums(dcum[i])
                if i == k.nb - 1:
                    col = col + jnp.where(last, dtot, 0.0)
                dcol[i] = jnp.where(lane_T == h, col, dcol[i])
                dxs[i] = jnp.where(mine, dxs[i] + dxj[i], dxs[i])
                row_scr[i, pl.ds(h, 1), :] = -csum[i]
        for n in range(len(tiles)):
            dcb_scr[n] += dcb[n]
        for i, r in enumerate(blocks):
            col_scr[0, r] += dcol[i]
            dx_ref[r, pl.ds(off, W)] = (dxs[i] * DT[r]).astype(dx_ref.dtype)
            v = dxs[i] * xf[r]                             # d dt_s, per lane
            ddt = 0.0
            for j in range(G):
                ddt = jnp.where(lane_T == g * G + j, jnp.sum(
                    jnp.where(head_T == j, v, 0.0), axis=1, keepdims=True),
                    ddt)
            col_scr[1, r] += ddt
        return carry

    jax.lax.fori_loop(0, HP // W, group, 0)

    for n, (i, kk) in enumerate(tiles):
        dcb = dcb_scr[n].astype(dtype)
        dcq_scr[blocks[i]] += _dot(dcb, k.Bm[blocks[kk]])
        dbq_scr[blocks[kk]] += _dot_tn(dcb, k.Cm[blocks[i]])
    dc_ref[...] = dcq_scr[...].astype(dc_ref.dtype)
    db_ref[...] = dbq_scr[...].astype(db_ref.dtype)
    rows = [row_scr[i].T for i in range(k.nb)]            # [T, H] each
    dcum = col_scr[0][:, :H] + (jnp.concatenate(rows, axis=0) if k.nb > 1
                                else rows[0])
    da = _scan_rows(dcum, reverse=True)                    # d a_t, [Q, H]
    ddt_ref[...] = (col_scr[1][:, :H] + a_ref[...] * da).astype(
        ddt_ref.dtype)
    da_ref[...] += jnp.sum(k.dt * da, axis=0, keepdims=True)

    @pl.when(c == pl.num_programs(1) - 1)
    def _end():
        ds0_ref[...] = ds_scr[...]


def _specs(B, L, H, HP, N, Q, reverse):
    nc = L // Q
    ch = (lambda c: nc - 1 - c) if reverse else (lambda c: c)
    return dict(
        rows=lambda w: pl.BlockSpec((None, Q, w), lambda b, c: (b, ch(c), 0)),
        per_b=pl.BlockSpec((None, N, HP), lambda b, c: (b, 0, 0)),
        per_chunk=pl.BlockSpec((None, None, N, HP),
                               lambda b, c: (b, ch(c), 0, 0)),
        A=pl.BlockSpec((1, H), lambda b, c: (0, 0)))


def _params():
    return pltpu.CompilerParams(dimension_semantics=("parallel",
                                                     "arbitrary"),
                                vmem_limit_bytes=VMEM_LIMIT)


def _forward(x, dt, A, Bm, Cm, s0, *, chunk, interpret, save_states):
    B, L, HP = x.shape
    H, N = dt.shape[-1], Bm.shape[-1]
    P = HP // H
    Q = chunk
    T = sub_tile(Q)
    nc = L // Q
    sp = _specs(B, L, H, HP, N, Q, reverse=False)
    kernel = functools.partial(_fwd_kernel, T=T,
                               G=lane_group(H, P), P=P,
                               has_init=s0 is not None,
                               save_states=save_states)
    in_specs = [sp["rows"](HP), sp["rows"](H), sp["A"], sp["rows"](N),
                sp["rows"](N)] + ([sp["per_b"]] if s0 is not None else [])
    out_specs = [sp["rows"](HP), sp["per_b"]]
    out_shape = [jax.ShapeDtypeStruct((B, L, HP), x.dtype),
                 jax.ShapeDtypeStruct((B, N, HP), F32)]
    if save_states:
        out_specs.append(sp["per_chunk"])
        out_shape.append(jax.ShapeDtypeStruct((B, nc, N, HP), F32))
    args = [x, dt, A.reshape(1, H).astype(F32), Bm, Cm]
    args += [s0.astype(F32)] if s0 is not None else []
    return pl.pallas_call(
        kernel, grid=(B, nc), in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((N, HP), F32),
                        pltpu.VMEM((Q // T, H, T), F32)],
        compiler_params=_params(), interpret=interpret,
        name="ssd_scan_fwd")(*args)


def _backward(x, dt, A, Bm, Cm, states, dy, dsf, *, chunk, interpret):
    B, L, HP = x.shape
    H, N = dt.shape[-1], Bm.shape[-1]
    P = HP // H
    Q = chunk
    T = sub_tile(Q)
    nb = Q // T
    sp = _specs(B, L, H, HP, N, Q, reverse=True)
    kernel = functools.partial(_bwd_kernel, T=T, G=lane_group(H, P), P=P)
    return pl.pallas_call(
        kernel, grid=(B, L // Q),
        in_specs=[sp["rows"](HP), sp["rows"](H), sp["A"], sp["rows"](N),
                  sp["rows"](N), sp["per_chunk"], sp["rows"](HP),
                  sp["per_b"]],
        out_specs=[sp["rows"](HP), sp["rows"](H), sp["rows"](N),
                   sp["rows"](N),
                   pl.BlockSpec((None, 1, H), lambda b, c: (b, 0, 0)),
                   sp["per_b"]],
        out_shape=[jax.ShapeDtypeStruct((B, L, HP), x.dtype),
                   jax.ShapeDtypeStruct((B, L, H), dt.dtype),
                   jax.ShapeDtypeStruct((B, L, N), Bm.dtype),
                   jax.ShapeDtypeStruct((B, L, N), Cm.dtype),
                   jax.ShapeDtypeStruct((B, 1, H), F32),
                   jax.ShapeDtypeStruct((B, N, HP), F32)],
        scratch_shapes=[pltpu.VMEM((N, HP), F32), pltpu.VMEM((nb, H, T), F32),
                        pltpu.VMEM((nb * (nb + 1) // 2, T, T), F32),
                        pltpu.VMEM((Q, N), F32), pltpu.VMEM((Q, N), F32),
                        pltpu.VMEM((2, Q, LANES), F32),
                        pltpu.VMEM((nb, H, T), F32)],
        compiler_params=_params(), interpret=interpret,
        name="ssd_scan_bwd")(x, dt, A.reshape(1, H).astype(F32), Bm, Cm,
                             states, dy, dsf)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan(x, dt, A, Bm, Cm, s0, chunk, interpret):
    y, sf = _forward(x, dt, A, Bm, Cm, s0, chunk=chunk, interpret=interpret,
                     save_states=False)
    return y, sf


def _scan_fwd(x, dt, A, Bm, Cm, s0, chunk, interpret):
    y, sf, states = _forward(x, dt, A, Bm, Cm, s0, chunk=chunk,
                             interpret=interpret, save_states=True)
    return (y, sf), (x, dt, A, Bm, Cm, states, s0)


def _scan_bwd(chunk, interpret, res, cts):
    x, dt, A, Bm, Cm, states, s0 = res
    dy, dsf = cts
    # traced apart from the forward: name the scope again
    with jax.named_scope(SSD_SCOPE):
        dx, ddt, dB, dC, dA, ds0 = _backward(
            x, dt, A, Bm, Cm, states, dy.astype(x.dtype), dsf.astype(F32),
            chunk=chunk, interpret=interpret)
        dA = jnp.sum(dA, axis=(0, 1)).astype(A.dtype)
    return dx, ddt, dA, dB, dC, (None if s0 is None else ds0)


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssd_scan_kernel(x, dt, A, Bm, Cm, *, chunk, initial_state=None,
                    interpret=False):
    """The chunked SSD scan on the Pallas kernels, differentiable.

    x [B,L,H,P]; dt [B,L,H] (positive); A [H] (negative); Bm, Cm [B,L,N];
    initial_state [B,H,P,N] or None.  ``chunk`` must divide L.  Returns
    y [B,L,H,P] in x's dtype, and the final state [B,H,P,N] in float32.
    The kernels see x and y as [B,L,H*P] and the state as [B,N,H*P].
    """
    B, L, H, P = x.shape
    N = Bm.shape[-1]
    HP = H * P
    s0 = None
    if initial_state is not None:
        s0 = initial_state.reshape(B, HP, N).swapaxes(1, 2)
    y, sf = _scan(x.reshape(B, L, HP), dt, A, Bm, Cm, s0, chunk, interpret)
    return (y.reshape(x.shape),
            sf.swapaxes(1, 2).reshape(B, H, P, N))
