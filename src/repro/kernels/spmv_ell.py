"""Pallas TPU kernels: ELL SpMV (+ fused Galerkin residual), two memory plans.

The iterative-solver hot loop is ``y = K·x`` on the assembled operator.  FEM
meshes have bounded valence, so ELLPACK (fixed nnz/row ``L``, padded) is the
TPU-friendly layout: the row dimension rides sublanes/grid, the ``L`` slots
are a small unrolled reduction, and the only awkward op — the gather
``x[cols]`` — is a 1-D dynamic gather.

Two kernels share the layout:

* :func:`spmv_ell` / :func:`galerkin_residual_ell` — the **broadcast** plan:
  ``x`` rides a VMEM BlockSpec replicated to every row block.  VMEM is
  (2·BN·L + N + BN) elements, so N is capped at VMEM scale (~1e5–1e6 f32).
* :func:`spmv_ell_stream` / :func:`galerkin_residual_ell_stream` — the
  **streaming** plan: every operand lives in HBM (``memory_space=ANY``); row
  blocks of ``vals``/``cols`` (and the per-block window of ``x``) are
  double-buffered into VMEM scratch with ``make_async_copy``, results DMA
  back out per block.  VMEM is ``nbuf·(BN·L·(w+4) + W·w) + BN·w`` bytes for
  element width ``w`` — independent of N, so N is bounded by HBM only.

The streaming gather needs each row block's columns inside a bounded window
``[start_b, start_b + W)``: static per-block windows are precomputed from the
column table (see :func:`_stream_plan`) and ``W`` is the widest one.  FEM
meshes with locality-preserving DoF orderings (the structured meshes here are
lexicographic) keep ``W`` near the matrix bandwidth; a scrambled ordering
inflates ``W`` toward N and the plan degenerates to the broadcast one —
``stream_window`` is recorded through :mod:`repro.telemetry` so regressions
are visible.

``interpret`` resolves from the active JAX backend
(:func:`repro.kernels.interpret.resolve_interpret`): the Mosaic path on TPU,
the (DMA-emulating) interpreter elsewhere — so CPU CI runs the same kernel
logic and real hardware never silently interprets.  Tests override it per
call (``interpret=``).

Neither plan compiles for a TPU today: Mosaic rejects the in-kernel 1-D
gather ``jnp.take(x, cols)`` with ``NotImplementedError: Only 2D gather is
supported`` (f32 and f64), and the broadcast plan's f64 lowering also fails
with ``ZeroDivisionError: integer modulo by zero``.  The ``ell_pallas`` /
``ell_stream`` matvec backends therefore refuse a TPU backend up front
(:mod:`repro.core.matvec`).

The fused residual variants compute ``r = K·u − f`` in the same kernel — the
TensorPILS training objective's inner op (one pass, no extra HBM round-trip).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import telemetry
from ..telemetry import annotate
from .interpret import resolve_interpret

__all__ = [
    "spmv_ell",
    "galerkin_residual_ell",
    "spmv_ell_stream",
    "galerkin_residual_ell_stream",
    "stream_vmem_bytes",
    "autotune_stream",
]

BLOCK_N = 4096
N_BUFFERS = 2           # double buffering: DMA block b+1 while computing b
_LANE = 128             # 1-D window length granularity (TPU lane count)


# ---------------------------------------------------------------------------
# Broadcast-plan kernels (x replicated into VMEM per row block)
# ---------------------------------------------------------------------------

def _spmv_kernel(vals_ref, cols_ref, x_ref, out_ref):
    vals = vals_ref[...]                     # (BN, L)
    cols = cols_ref[...]                     # (BN, L)
    x = x_ref[...]                           # (N,)
    gathered = jnp.take(x, cols, axis=0)     # 1-D dynamic gather
    out_ref[...] = jnp.sum(vals * gathered, axis=1)


def _residual_kernel(vals_ref, cols_ref, x_ref, f_ref, out_ref):
    vals = vals_ref[...]
    cols = cols_ref[...]
    x = x_ref[...]
    gathered = jnp.take(x, cols, axis=0)
    out_ref[...] = jnp.sum(vals * gathered, axis=1) - f_ref[...]


def _pad_rows(a, n_pad, fill=0):
    return jnp.pad(a, ((0, n_pad - a.shape[0]),) + ((0, 0),) * (a.ndim - 1),
                   constant_values=fill)


# static column tables staged once per (layout, block_n): int32 cast + row
# padding hoisted out of the solve loop (the id-keyed host arrays are kept
# alive by the cache entry, FIFO-bounded like the core's device mirrors)
_STAGED_COLS: dict[tuple[int, int], tuple] = {}
_STAGED_LIMIT = 128


def _staged_cols(cols, block_n: int):
    """``cols`` → (padded int32 device array, n_pad); cached for static
    (non-tracer) column tables, traced fallback otherwise."""
    n = cols.shape[0]
    n_pad = -(-n // block_n) * block_n
    if isinstance(cols, jax.core.Tracer):
        return _pad_rows(cols.astype(jnp.int32), n_pad), n_pad
    key = (id(cols), block_n)
    hit = _STAGED_COLS.get(key)
    if hit is not None:
        return hit[1], n_pad
    staged = jnp.asarray(_pad_host_cols(np.asarray(cols), n_pad))
    while len(_STAGED_COLS) >= _STAGED_LIMIT:
        _STAGED_COLS.pop(next(iter(_STAGED_COLS)))
    _STAGED_COLS[key] = (cols, staged)
    return staged, n_pad


def _pad_host_cols(cols_np: np.ndarray, n_pad: int) -> np.ndarray:
    n, l = cols_np.shape
    out = np.empty((n_pad, l), dtype=np.int32)
    out[:n] = cols_np
    # padded rows self-reference (row index < n_pad); their vals are zero
    out[n:] = np.arange(n, n_pad, dtype=np.int32)[:, None]
    return out


@functools.partial(jax.jit, static_argnames=("interpret", "block_n"))
def _spmv_ell_padded(vals, cols_p, x, *, interpret: bool, block_n: int):
    n, l = vals.shape
    n_pad = cols_p.shape[0]
    vals_p = _pad_rows(vals, n_pad)
    x_p = _pad_rows(x, n_pad)  # padded cols may self-reference rows ≥ n
    grid = (n_pad // block_n,)
    with annotate("tg.pallas.spmv_ell"):
        out = pl.pallas_call(
            _spmv_kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_n, l), lambda i: (i, 0)),
                pl.BlockSpec((block_n, l), lambda i: (i, 0)),
                pl.BlockSpec((n_pad,), lambda i: (0,)),
            ],
            out_specs=pl.BlockSpec((block_n,), lambda i: (i,)),
            out_shape=jax.ShapeDtypeStruct((n_pad,), vals.dtype),
            interpret=interpret,
        )(vals_p, cols_p, x_p)
    return out[:n]


@functools.partial(jax.jit, static_argnames=("interpret", "block_n"))
def _residual_ell_padded(vals, cols_p, u, f, *, interpret: bool, block_n: int):
    n, l = vals.shape
    n_pad = cols_p.shape[0]
    vals_p = _pad_rows(vals, n_pad)
    u_p = _pad_rows(u, n_pad)
    f_p = jnp.pad(f, (0, n_pad - n))
    grid = (n_pad // block_n,)
    with annotate("tg.pallas.galerkin_residual_ell"):
        out = pl.pallas_call(
            _residual_kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_n, l), lambda i: (i, 0)),
                pl.BlockSpec((block_n, l), lambda i: (i, 0)),
                pl.BlockSpec((n_pad,), lambda i: (0,)),
                pl.BlockSpec((block_n,), lambda i: (i,)),
            ],
            out_specs=pl.BlockSpec((block_n,), lambda i: (i,)),
            out_shape=jax.ShapeDtypeStruct((n_pad,), vals.dtype),
            interpret=interpret,
        )(vals_p, cols_p, u_p, f_p)
    return out[:n]


def spmv_ell(vals: jnp.ndarray, cols: jnp.ndarray, x: jnp.ndarray, *,
             interpret: bool | None = None, block_n: int = BLOCK_N):
    """vals/cols (N, L), x (N,) → y (N,) — broadcast plan.  Padded cols must
    self-reference rows with zero vals (the ELL builder guarantees this)."""
    itp = resolve_interpret(interpret)
    cols_p, _ = _staged_cols(cols, block_n)
    return _spmv_ell_padded(vals, cols_p, x, interpret=itp, block_n=block_n)


def galerkin_residual_ell(vals, cols, u, f, *, interpret: bool | None = None,
                          block_n: int = BLOCK_N):
    """Fused r = K·u − f (TensorPILS inner op) — broadcast plan."""
    itp = resolve_interpret(interpret)
    cols_p, _ = _staged_cols(cols, block_n)
    return _residual_ell_padded(vals, cols_p, u, f, interpret=itp,
                                block_n=block_n)


# ---------------------------------------------------------------------------
# Streaming-plan kernels: HBM-resident operands, DMA double buffering
# ---------------------------------------------------------------------------

class _StreamPlan:
    """Static per-(layout, block_n) streaming schedule: rebased column
    blocks, per-block x-window starts and the uniform window width W."""

    __slots__ = ("cols_local", "starts", "window", "n_pad", "x_len", "_keep")

    def __init__(self, cols_np: np.ndarray, block_n: int):
        n, l = cols_np.shape
        n_blocks = -(-n // block_n)
        n_pad = n_blocks * block_n
        cols_pad = np.empty((n_pad, l), dtype=np.int64)
        cols_pad[:n] = cols_np
        if n_pad > n:
            # padded rows get in-window dummies patched below (vals are zero)
            cols_pad[n:] = cols_np[n - 1, 0]
        blocks = cols_pad.reshape(n_blocks, block_n * l)
        lo = blocks.min(axis=1)
        hi = blocks.max(axis=1)
        width = int((hi - lo + 1).max()) if n_blocks else 1
        window = -(-width // _LANE) * _LANE
        starts = lo.astype(np.int32)
        local = (cols_pad - starts.astype(np.int64).repeat(block_n)[:, None])
        self.cols_local = local.astype(np.int32)           # in [0, W)
        self.starts = starts                               # (n_blocks,)
        self.window = window                               # W
        self.n_pad = n_pad
        self.x_len = int(max(n, (starts.astype(np.int64) + window).max()
                             if n_blocks else n))
        self._keep = None  # set by the cache: pins the id-keyed key object


_STREAM_PLANS: dict[tuple[int, int], _StreamPlan] = {}
_STREAM_PLANS_LIMIT = 64


def _stream_plan(cols, block_n: int) -> _StreamPlan:
    key = (id(cols), block_n)
    hit = _STREAM_PLANS.get(key)
    if hit is not None:
        return hit
    plan = _StreamPlan(np.asarray(cols), block_n)
    plan._keep = cols  # id stays valid while the entry lives
    while len(_STREAM_PLANS) >= _STREAM_PLANS_LIMIT:
        _STREAM_PLANS.pop(next(iter(_STREAM_PLANS)))
    _STREAM_PLANS[key] = plan
    telemetry.gauge_set("ell_stream_window", plan.window, block_n=block_n)
    return plan


def stream_vmem_bytes(n_rows: int, l: int, *, block_n: int = BLOCK_N,
                      nbuf: int = N_BUFFERS, window: int | None = None,
                      itemsize: int = 8) -> int:
    """VMEM footprint of the streaming kernel (independent of N): buffered
    vals + int32 cols + x windows, plus the output staging block."""
    w = window if window is not None else block_n + _LANE
    return nbuf * (block_n * l * (itemsize + 4) + w * itemsize) \
        + block_n * itemsize


def _stream_kernel(residual: bool, nbuf: int, block_n: int, window: int,
                   n_blocks: int, l: int,
                   starts_ref, vals_hbm, cols_hbm, x_hbm, *rest):
    if residual:
        f_hbm, out_hbm, vals_buf, cols_buf, x_buf, f_buf, out_buf, \
            sem_in, sem_out = rest
    else:
        out_hbm, vals_buf, cols_buf, x_buf, out_buf, sem_in, sem_out = rest
        f_hbm = f_buf = None

    def copies(j, slot):
        row0 = j * block_n
        cps = [
            pltpu.make_async_copy(vals_hbm.at[pl.ds(row0, block_n)],
                                  vals_buf.at[slot], sem_in.at[slot, 0]),
            pltpu.make_async_copy(cols_hbm.at[pl.ds(row0, block_n)],
                                  cols_buf.at[slot], sem_in.at[slot, 1]),
            pltpu.make_async_copy(x_hbm.at[pl.ds(starts_ref[j], window)],
                                  x_buf.at[slot], sem_in.at[slot, 2]),
        ]
        if residual:
            cps.append(
                pltpu.make_async_copy(f_hbm.at[pl.ds(row0, block_n)],
                                      f_buf.at[slot], sem_in.at[slot, 3])
            )
        return cps

    # warm-up: fill the pipeline (static unroll — nbuf, n_blocks are Python)
    for j in range(min(nbuf, n_blocks)):
        for cp in copies(j, j % nbuf):
            cp.start()

    def body(b, _):
        slot = jax.lax.rem(b, nbuf)
        for cp in copies(b, slot):
            cp.wait()
        gathered = jnp.take(x_buf[slot], cols_buf[slot], axis=0)  # (BN, L)
        y = jnp.sum(vals_buf[slot] * gathered, axis=1)
        if residual:
            y = y - f_buf[slot]
        # overlap: block b's buffers are consumed above — refill the slot
        # with block b+nbuf while the store below drains
        @pl.when(b + nbuf < n_blocks)
        def _prefetch():
            for cp in copies(b + nbuf, slot):
                cp.start()
        out_buf[...] = y
        out_cp = pltpu.make_async_copy(
            out_buf, out_hbm.at[pl.ds(b * block_n, block_n)], sem_out
        )
        out_cp.start()
        out_cp.wait()  # out_buf is reused next iteration
        return 0

    jax.lax.fori_loop(0, n_blocks, body, 0)


@functools.partial(jax.jit,
                   static_argnames=("interpret", "block_n", "nbuf", "window"))
def _spmv_stream_padded(vals, cols_local, x, starts, *, interpret: bool,
                        block_n: int, nbuf: int, window: int):
    n, l = vals.shape
    n_pad = cols_local.shape[0]
    n_blocks = n_pad // block_n
    x_len = x.shape[0]
    vals_p = _pad_rows(vals, n_pad)
    kernel = functools.partial(_stream_kernel, False, nbuf, block_n, window,
                               n_blocks, l)
    with annotate("tg.pallas.spmv_ell_stream"):
        out = pl.pallas_call(
            kernel,
            grid=(),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),     # starts
                pl.BlockSpec(memory_space=pltpu.ANY),      # vals (HBM)
                pl.BlockSpec(memory_space=pltpu.ANY),      # cols (HBM)
                pl.BlockSpec(memory_space=pltpu.ANY),      # x    (HBM)
            ],
            out_specs=pl.BlockSpec(memory_space=pltpu.ANY),
            out_shape=jax.ShapeDtypeStruct((n_pad,), vals.dtype),
            scratch_shapes=[
                pltpu.VMEM((nbuf, block_n, l), vals.dtype),
                pltpu.VMEM((nbuf, block_n, l), jnp.int32),
                pltpu.VMEM((nbuf, window), x.dtype),
                pltpu.VMEM((block_n,), vals.dtype),
                pltpu.SemaphoreType.DMA((nbuf, 3)),
                pltpu.SemaphoreType.DMA(()),
            ],
            interpret=interpret,
        )(starts, vals_p, cols_local, x)
    return out[:n]


@functools.partial(jax.jit,
                   static_argnames=("interpret", "block_n", "nbuf", "window"))
def _residual_stream_padded(vals, cols_local, u, f, starts, *,
                            interpret: bool, block_n: int, nbuf: int,
                            window: int):
    n, l = vals.shape
    n_pad = cols_local.shape[0]
    n_blocks = n_pad // block_n
    vals_p = _pad_rows(vals, n_pad)
    f_p = jnp.pad(f, (0, n_pad - n))
    kernel = functools.partial(_stream_kernel, True, nbuf, block_n, window,
                               n_blocks, l)
    with annotate("tg.pallas.galerkin_residual_ell_stream"):
        out = pl.pallas_call(
            kernel,
            grid=(),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pltpu.ANY),
                pl.BlockSpec(memory_space=pltpu.ANY),
                pl.BlockSpec(memory_space=pltpu.ANY),
                pl.BlockSpec(memory_space=pltpu.ANY),      # f (HBM)
            ],
            out_specs=pl.BlockSpec(memory_space=pltpu.ANY),
            out_shape=jax.ShapeDtypeStruct((n_pad,), vals.dtype),
            scratch_shapes=[
                pltpu.VMEM((nbuf, block_n, l), vals.dtype),
                pltpu.VMEM((nbuf, block_n, l), jnp.int32),
                pltpu.VMEM((nbuf, window), u.dtype),
                pltpu.VMEM((nbuf, block_n), f.dtype),
                pltpu.VMEM((block_n,), vals.dtype),
                pltpu.SemaphoreType.DMA((nbuf, 4)),
                pltpu.SemaphoreType.DMA(()),
            ],
            interpret=interpret,
        )(starts, vals_p, cols_local, u, f_p)
    return out[:n]


def _stream_x(x, plan: _StreamPlan):
    n = x.shape[0]
    return x if plan.x_len == n else jnp.pad(x, (0, plan.x_len - n))


def spmv_ell_stream(vals: jnp.ndarray, cols, x: jnp.ndarray, *,
                    interpret: bool | None = None, block_n: int = BLOCK_N,
                    nbuf: int = N_BUFFERS):
    """Streaming SpMV: vals/cols (N, L), x (N,) → y (N,) with every operand
    HBM-resident and VMEM usage independent of N (module docstring).
    ``cols`` must be a static (non-tracer) column table — the streaming
    schedule is a host precompute on it."""
    if isinstance(cols, jax.core.Tracer):
        raise TypeError(
            "spmv_ell_stream needs a static column table (the streaming "
            "window schedule is a host precompute); pass the ELL layout's "
            "numpy cols, or use spmv_ell for traced columns"
        )
    itp = resolve_interpret(interpret)
    plan = _stream_plan(cols, block_n)
    return _spmv_stream_padded(
        vals, jnp.asarray(plan.cols_local), _stream_x(x, plan),
        jnp.asarray(plan.starts), interpret=itp, block_n=block_n, nbuf=nbuf,
        window=plan.window,
    )


def galerkin_residual_ell_stream(vals, cols, u, f, *,
                                 interpret: bool | None = None,
                                 block_n: int = BLOCK_N,
                                 nbuf: int = N_BUFFERS):
    """Fused streaming residual r = K·u − f (see :func:`spmv_ell_stream`)."""
    if isinstance(cols, jax.core.Tracer):
        raise TypeError(
            "galerkin_residual_ell_stream needs a static column table; use "
            "galerkin_residual_ell for traced columns"
        )
    itp = resolve_interpret(interpret)
    plan = _stream_plan(cols, block_n)
    return _residual_stream_padded(
        vals, jnp.asarray(plan.cols_local), _stream_x(u, plan), f,
        jnp.asarray(plan.starts), interpret=itp, block_n=block_n, nbuf=nbuf,
        window=plan.window,
    )


# ---------------------------------------------------------------------------
# Autotune hook: pick (block_n, nbuf) by measurement, record via telemetry
# ---------------------------------------------------------------------------

_AUTOTUNED: dict[tuple[int, int], tuple[int, int]] = {}


def autotune_stream(vals, cols, x, *,
                    block_candidates=(1024, 4096, 8192),
                    nbuf_candidates=(2, 3),
                    interpret: bool | None = None,
                    iters: int = 3) -> tuple[int, int]:
    """Measure :func:`spmv_ell_stream` over ``block_n × nbuf`` candidates and
    return the fastest pair.  Results are cached per (layout, N) and every
    measurement lands in the telemetry registry
    (``histogram ell_stream_autotune_us`` with block_n/nbuf labels;
    ``gauge ell_stream_block_n`` / ``ell_stream_nbuf`` hold the winner) so
    tuning sweeps are inspectable offline."""
    import time

    key = (id(cols), vals.shape[0])
    hit = _AUTOTUNED.get(key)
    if hit is not None:
        return hit
    n = vals.shape[0]
    best, best_t = None, float("inf")
    for bn in block_candidates:
        if bn > max(n, _LANE):
            continue
        for nb in nbuf_candidates:
            out = spmv_ell_stream(vals, cols, x, interpret=interpret,
                                  block_n=bn, nbuf=nb)
            jax.block_until_ready(out)  # compile outside the timed loop
            t0 = time.perf_counter()
            for _ in range(iters):
                jax.block_until_ready(
                    spmv_ell_stream(vals, cols, x, interpret=interpret,
                                    block_n=bn, nbuf=nb)
                )
            us = (time.perf_counter() - t0) / iters * 1e6
            telemetry.histogram_observe("ell_stream_autotune_us", us,
                                        block_n=bn, nbuf=nb)
            if us < best_t:
                best, best_t = (bn, nb), us
    if best is None:
        best = (min(BLOCK_N, max(_LANE, n)), N_BUFFERS)
    telemetry.gauge_set("ell_stream_block_n", best[0])
    telemetry.gauge_set("ell_stream_nbuf", best[1])
    _AUTOTUNED[key] = best
    return best
