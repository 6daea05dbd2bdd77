"""Fused four-step FFT as a Pallas TPU kernel — the templateFFT analog.

The reference's single-GPU engine is a runtime kernel generator that stages a
whole 1D/2D FFT through shared memory in one launch (``shaderGenFFT``,
``templateFFT/src/templateFFT.cpp:4699``; the scheduler splits an axis into
shared-memory-sized passes, ``:3941-4100``). The TPU-native equivalent is NOT
a butterfly kernel — TPU FLOPs live in the 128x128 MXU, not in a scalar/vector
butterfly network — but the *fusion* idea carries over: this module stages the
entire four-step decomposition of one axis

    n = n1 * n2,  x viewed as A[j1, j2]
    G[j2, k1] = sum_j1 A[j1, j2] * W1[j1, k1]     (MXU matmul, contract j1)
    H[j2, k1] = G * w_n^{j2*k1}                   (VPU twiddle)
    Z[k1, k2] = sum_j2 H[j2, k1] * W2[j2, k2]     (MXU matmul, contract j2)
    X[k1 + n1*k2] = Z[k1, k2]                     (VMEM transpose)

through VMEM in ONE kernel per batch tile: one HBM read and one HBM write per
transform, where the un-fused einsum path (``ops/dft_matmul.py``) materializes
every intermediate stage to HBM (XLA cannot fuse matmul -> matmul). Complex
data travels as separate real/imaginary float32 planes (Mosaic has no complex
dtype); each complex matmul is four real MXU matmuls at HIGHEST precision.

Twiddle/DFT-matrix LUTs are precomputed on the host in float64 and cast to
float32 — the same plan-time LUT discipline as the reference
(``templateFFT.cpp:5063-5154``).

Scope: complex64, composite n with a balanced split n1*n2 (n1, n2 <= 256 —
one kernel covers n up to 65536; longer axes fall back to the recursive
matmul executor). The inverse is the conjugate-matrix kernel with the 1/n
scale applied by the caller (numpy convention, like every executor here).

The ``pallas`` executor's complex64 path is :func:`fft_planes` (the last
section): float32 planes in and out, one in-place pass per axis whose
length is r * 128 (r <= 8), factored so the MXU contracts 128 wide; other
axes take :func:`fft_along_axis` above.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.compat import (
    pvary, shape_dtype_struct, tpu_compiler_params, typeof_vma,
)
from .dft_matmul import _dft_matrix_np

# Largest per-stage DFT factor the kernel accepts; 256 keeps every LUT and
# matmul comfortably MXU/VMEM-sized and covers n <= 65536 in one kernel.
MAX_FACTOR = 256

# VMEM working-set budget per batch tile (bytes). Hardware-measured (v5e):
# Mosaic's scoped stack holds ~12 [tile, n] float32 planes live (re/im at
# each staged intermediate plus the transpose copies), and the grid
# pipeline double-buffers the in/out tiles on top — ~1.5 MiB of budget per
# 16 n-rows. The budget is sized so the whole footprint stays inside
# _VMEM_LIMIT with headroom (a 512-row tile at n=512 measured 48 MiB of
# scoped stack).
_VMEM_BUDGET = 2 * 1024 * 1024

# Mosaic scoped-VMEM ceiling requested via CompilerParams. The default
# scoped limit (16 MiB on v5e) rejects any usefully-sized tile; the chip
# has 128 MiB of VMEM and granting the kernel most of it is the same
# decision the reference makes sizing shared memory per workgroup
# (templateFFT.cpp:3941-4100 maxSharedMemSize).
_VMEM_LIMIT = 100 * 1024 * 1024


def split_for(n: int) -> tuple[int, int] | None:
    """(n1, n2) factor pair the kernel runs, or None.

    The bounded-split decision comes from the native scheduler
    (``dfft_balanced_split`` with the kernel's MAX_FACTOR bound — the
    VMEM-bounded analog of the reference's shared-memory-bounded axis split,
    ``templateFFT.cpp:3941-4100``). The balanced pair minimizes flops
    (8N(n1+n2)) but runs tiny stage matmuls (16x32 at n=512 — a nearly
    idle 128-lane MXU when the pack probe rejects widening);
    ``DFFT_PALLAS_SPLIT`` (same ``N=AxB,...`` syntax as DFFT_MM_SPLIT)
    overrides per length for the hardware sweeps, trading flops for a
    stage factor at the 128 MXU edge (e.g. 512=4x128). Read at trace
    time, like DFFT_MM_PRECISION: the tile jits capture the split, so
    in-process sweepers must clear their caches (tune_pallas does)."""
    import os

    from .. import native

    spec = os.environ.get("DFFT_PALLAS_SPLIT", "").strip()
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        try:
            key, val = part.split("=")
            key = int(key)
            a, b = (int(v) for v in val.split("x"))
        except ValueError:
            raise ValueError(
                f"DFFT_PALLAS_SPLIT entry {part!r} is not N=AxB") from None
        if key == n:
            if a * b != n or not (1 < a <= MAX_FACTOR) \
                    or not (1 < b <= MAX_FACTOR):
                raise ValueError(
                    f"DFFT_PALLAS_SPLIT {part!r}: need A*B == {n} with "
                    f"factors in (1, {MAX_FACTOR}]")
            return a, b
    return native.balanced_split(n, MAX_FACTOR)


def eligible(n: int) -> bool:
    """Axis lengths the fused kernel handles (others fall back)."""
    return n >= 64 and split_for(n) is not None


def _tile_rows(env_name: str, bytes_per_row: int, floor: int) -> int:
    """Shared tile-size model: power of two, >= ``floor``, VMEM-budgeted;
    ``env_name`` overrides for hardware tuning sweeps (single source for
    the row and column kernels so budget changes cannot desynchronize
    them)."""
    import os

    env = os.environ.get(env_name)
    if env:
        return int(env)
    rows = max(floor, _VMEM_BUDGET // bytes_per_row)
    return 1 << min(10, int(math.log2(rows)))


def batch_tile(n: int) -> int:
    """Batch rows per grid step for the 1D kernel."""
    return _tile_rows("DFFT_PALLAS_TILE", 4 * 4 * n, 8)


def _tables_np(n: int, forward: bool, g1: int = 1, g2: int = 1):
    """(W1, T, W2) float32 LUT triple for n = n1*n2, host-exact float64.

    The split is resolved HERE (so a DFFT_PALLAS_SPLIT change between
    calls is honored) and passed into the cached builder — the cache key
    carries (n1, n2), never a stale environment read."""
    n1, n2 = split_for(n)
    return _tables_np_cached(n, n1, n2, forward, g1, g2)


@functools.lru_cache(maxsize=None)
def _tables_np_cached(n: int, n1: int, n2: int, forward: bool,
                      g1: int = 1, g2: int = 1):
    """W1[j1, k1] is the n1-point DFT matrix, W2[j2, k2] the n2-point one,
    and T[j2, k1] = w_n^{j2*k1} the inter-stage twiddle laid out to match
    the first stage's [j2, k1] output. ``g1``/``g2`` > 1 widen the stage
    matrices to block-diagonal I_g (x) W — ``g`` independent DFTs as one
    MXU-width matmul (identical sums; the off-block zeros are exact), the
    packing that lifts a sub-128 factor's systolic-array utilization from
    (n/128)^2 to ~full (see ``dft_matmul.pack_factor``).
    """
    from .dft_matmul import _blockdiag_dft_np
    w1 = _blockdiag_dft_np(n1, g1, forward)
    w2 = _blockdiag_dft_np(n2, g2, forward)
    sign = -2j if forward else 2j
    jk = np.outer(np.arange(n2), np.arange(n1))
    t = np.exp(sign * np.pi * (jk % n) / n)
    f32 = lambda a: np.ascontiguousarray(a.astype(np.complex64))
    return f32(w1), f32(t), f32(w2)


def _interpret_mode() -> bool:
    """True on the CPU test backend (kernels run in the Pallas
    interpreter; shard_map calls route to the jnp mirrors).
    ``DFFT_FORCE_REAL_LOWERING=1`` (``utils.compat.force_real_lowering``,
    shared with the exchange mirrors) forces the REAL pallas_call path
    regardless of backend — not executable on CPU, but it lets
    ``jax.export``-based lowering tests build the actual Mosaic module
    (including the shard_map/vma path) on a chipless host
    (tests/test_tpu_lowering.py)."""
    from ..utils.compat import force_real_lowering

    if force_real_lowering():
        return False
    return jax.default_backend() == "cpu"


def _vma(x) -> frozenset:
    """Varying-across-mesh-axes set of a traced value (empty outside
    shard_map); pallas_call outputs must declare the same set."""
    return typeof_vma(x)


def _mm(a, b):
    from .dft_matmul import mm_precision

    return lax.dot_general(
        a, b, (((1,), (0,)), ((), ())),
        precision=mm_precision(),
        preferred_element_type=jnp.float32,
    )


def _four_step_pass(a3r, a3i, w1r, w1i, tr, ti, w2r, w2i, g1=1, g2=1):
    """One four-step DFT pass contracting the factor dims of [rows, n1, n2]
    planes (the transform axis pre-split to (n1, n2) by the caller), shared
    by the row and column kernels and the fusion tier's. With ``g1``/``g2`` > 1 the stage
    matrices arrive block-diagonal (I_g (x) W, see ``_tables_np``) and the
    row dim is regrouped so each matmul contracts a full MXU-width g*n
    lanes instead of a sub-128 factor — the reshapes change the lane dim,
    which Mosaic implements as VMEM relayouts (cheap next to a 98%-idle
    systolic array). Returns [rows, n2, n1] planes — flat (k2, k1) IS the
    transformed axis in natural order (k = k1 + n1*k2)."""
    rows, n1, n2 = a3r.shape
    # A[b, j1, j2] -> [b*j2, j1] so stage 1 contracts j1 on the MXU.
    sr = a3r.transpose(0, 2, 1).reshape(rows * n2 // g1, g1 * n1)
    si = a3i.transpose(0, 2, 1).reshape(rows * n2 // g1, g1 * n1)
    gr = _mm(sr, w1r) - _mm(si, w1i)
    gi = _mm(sr, w1i) + _mm(si, w1r)
    # Twiddle on [b, j2, k1] (T broadcast over the batch).
    gr = gr.reshape(rows, n2, n1)
    gi = gi.reshape(rows, n2, n1)
    hr = gr * tr - gi * ti
    hi = gr * ti + gi * tr
    # Stage 2 contracts j2: [b*k1, j2] @ W2 -> Z[b, k1, k2].
    hr = hr.transpose(0, 2, 1).reshape(rows * n1 // g2, g2 * n2)
    hi = hi.transpose(0, 2, 1).reshape(rows * n1 // g2, g2 * n2)
    zr = _mm(hr, w2r) - _mm(hi, w2i)
    zi = _mm(hr, w2i) + _mm(hi, w2r)
    # Output flat index k = k1 + n1*k2: emit Z^T = [b, k2, k1].
    zr = zr.reshape(rows, n1, n2).transpose(0, 2, 1)
    zi = zi.reshape(rows, n1, n2).transpose(0, 2, 1)
    return zr, zi


@functools.lru_cache(maxsize=None)
def _pack_probe_ok(n1: int, n2: int, g1: int, g2: int) -> bool:
    """Per-config Mosaic compile probe for the packed kernels' lane-changing
    reshapes. The packed stage matmuls regroup rows with reshapes that
    change the lane (last) dimension; interpret-mode tests cannot prove a
    given Mosaic version lowers them — and acceptance can depend on the
    pack widths themselves (a 128-lane-aligned g=8 relayout may lower
    while a 120-lane g=12 one does not) — so on a real backend a one-block
    kernel with the exact (n1, n2, g1, g2) about to be used is compiled
    once per process, and the block-diagonal packing is auto-disabled for
    that config (g1=g2=1 — correct, just slower) if the compiler rejects
    it. ``DFFT_PALLAS_PACK=0/1`` overrides the probe in either direction."""
    from ..utils.compat import force_real_lowering

    chipless_lowering = (jax.default_backend() == "cpu"
                         and force_real_lowering())
    if jax.default_backend() == "cpu" and not chipless_lowering:
        return True  # interpret mode executes the reshapes directly
    try:
        n = n1 * n2
        # Smallest row tile the kernel's regroup reshapes accept: rows*n2
        # divisible by g1 and rows*n1 by g2 (same invariant pack_factor
        # guarantees for the real tile).
        bt = next(r for r in range(8, 8 * g1 * g2 + 9)
                  if (r * n2) % g1 == 0 and (r * n1) % g2 == 0)
        w1, t, w2 = _tables_np(n, True, g1, g2)
        consts = [jnp.asarray(p) for m in (w1, t, w2)
                  for p in (m.real, m.imag)]
        lut_specs = [
            pl.BlockSpec(m.shape, lambda i: (0, 0), memory_space=pltpu.VMEM)
            for m in (w1, w1, t, t, w2, w2)
        ]
        x_spec = pl.BlockSpec((bt, n1, n2), lambda i: (i, 0, 0),
                              memory_space=pltpu.VMEM)
        y_spec = pl.BlockSpec((bt, n2, n1), lambda i: (i, 0, 0),
                              memory_space=pltpu.VMEM)
        call = pl.pallas_call(
            _make_kernel(n1, n2, g1, g2),
            grid=(1,),
            in_specs=lut_specs + [x_spec, x_spec],
            out_specs=(y_spec, y_spec),
            out_shape=(
                jax.ShapeDtypeStruct((bt, n2, n1), jnp.float32),
                jax.ShapeDtypeStruct((bt, n2, n1), jnp.float32),
            ),
            compiler_params=tpu_compiler_params(
                dimension_semantics=("parallel",),
                vmem_limit_bytes=_VMEM_LIMIT,
            ),
        )
        z = jnp.zeros((bt, n1, n2), jnp.float32)
        if chipless_lowering:
            # No chip to compile against: probe the Mosaic front end via
            # the TPU export pipeline instead, so force-real lowering
            # tests exercise the same pack gate the real backend would
            # (target-stage acceptance still differs — the hardware
            # probe owns that).
            from jax import export as _export

            _export.export(jax.jit(lambda a, b: call(*consts, a, b)),
                           platforms=["tpu"])(z, z)
            return True
        jax.jit(lambda a, b: call(*consts, a, b)).lower(z, z).compile()
        return True
    except Exception:  # noqa: BLE001 — any rejection means fall back
        return False


def _packs(n1: int, n2: int, rows: int) -> tuple[int, int]:
    """(g1, g2) block-diagonal pack factors for one four-step pass over
    [rows, n1, n2] tiles. ``DFFT_PALLAS_PACK=0`` force-disables,
    ``=1`` force-enables; unset, a one-time compile probe
    (:func:`_pack_probe_ok`) decides whether this Mosaic version accepts
    the packed kernels' lane-changing reshapes."""
    import os

    from .dft_matmul import pack_factor

    env = os.environ.get("DFFT_PALLAS_PACK")
    if env == "0":
        return 1, 1
    g1 = pack_factor(n1, rows * n2)
    g2 = pack_factor(n2, rows * n1)
    if (g1, g2) == (1, 1):
        return 1, 1
    if env is None and not _pack_probe_ok(n1, n2, g1, g2):
        from ..utils import metrics as _metrics

        _metrics.inc("pallas_pack_disabled", n1=n1, n2=n2)
        return 1, 1
    return g1, g2


def _make_kernel(n1: int, n2: int, g1: int, g2: int):
    def kernel(w1r, w1i, tr, ti, w2r, w2i, xr, xi, yr, yi):
        zr, zi = _four_step_pass(
            xr[:], xi[:],
            w1r[:], w1i[:], tr[:], ti[:], w2r[:], w2i[:],
            g1=g1, g2=g2,
        )
        yr[:] = zr
        yi[:] = zi

    return kernel


@functools.partial(jax.jit, static_argnames=("n", "forward", "interpret"))
def _fft_tiles(xr, xi, *, n: int, forward: bool, interpret: bool):
    """Batched length-n DFT of [batch, n] float32 re/im planes; batch must be
    a multiple of the tile size."""
    n1, n2 = split_for(n)
    batch = xr.shape[0]
    bt = min(batch_tile(n), batch)
    grid = batch // bt
    g1, g2 = _packs(n1, n2, bt)

    w1, t, w2 = _tables_np(n, forward, g1, g2)
    consts = [jnp.asarray(p) for m in (w1, t, w2) for p in (m.real, m.imag)]
    vma = _vma(xr)
    if vma:
        # Under shard_map every kernel operand must carry the data's
        # varying-axes set; the replicated LUTs are marked explicitly.
        consts = [pvary(c, tuple(vma)) for c in consts]

    lut_specs = [
        pl.BlockSpec(m.shape, lambda i: (0, 0), memory_space=pltpu.VMEM)
        for m in (w1, w1, t, t, w2, w2)
    ]
    x_spec = pl.BlockSpec((bt, n1, n2), lambda i: (i, 0, 0),
                          memory_space=pltpu.VMEM)
    y_spec = pl.BlockSpec((bt, n2, n1), lambda i: (i, 0, 0),
                          memory_space=pltpu.VMEM)

    yr, yi = pl.pallas_call(
        _make_kernel(n1, n2, g1, g2),
        grid=(grid,),
        in_specs=lut_specs + [x_spec, x_spec],
        out_specs=(y_spec, y_spec),
        # Under shard_map the operands carry a varying-across-mesh-axes set;
        # the outputs vary the same way (per-device batches are independent).
        out_shape=(
            shape_dtype_struct((batch, n2, n1), jnp.float32,
                               vma=_vma(xr)),
            shape_dtype_struct((batch, n2, n1), jnp.float32,
                               vma=_vma(xr)),
        ),
        cost_estimate=pl.CostEstimate(
            flops=8 * batch * n * (g1 * n1 + g2 * n2),
            bytes_accessed=4 * batch * n * 4,
            transcendentals=0,
        ),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(*consts, xr.reshape(batch, n1, n2), xi.reshape(batch, n1, n2))
    return yr.reshape(batch, n), yi.reshape(batch, n)


def _make_kernel_strided(n1: int, n2: int, g1: int, g2: int):
    """Strided kernel: four-step DFT over the LEADING axis of [n1, n2, ct]
    tiles (transform axis pre-split, a column chunk trailing) — the
    ``radixStrided`` role of the reference's codegen
    (``templateFFT.cpp:1760``): transform a strided axis without a global
    transpose. The HBM layout never changes; the reorders run on the tile
    in VMEM. ``g1``/``g2`` are block-diagonal pack factors (``_packs``).
    Output tiles are [n2, n1, ct] (flat (k2, k1) = the transformed
    axis in natural order)."""

    def kernel(w1r, w1i, tr, ti, w2r, w2i, xr, xi, yr, yi):
        ct = xr.shape[-1]
        # Stage 1 contracts j1: [j1, j2, c] -> [j2, c, j1] -> [j2*c, j1].
        ar = xr[:].transpose(1, 2, 0).reshape(n2 * ct // g1, g1 * n1)
        ai = xi[:].transpose(1, 2, 0).reshape(n2 * ct // g1, g1 * n1)
        gr = _mm(ar, w1r[:]) - _mm(ai, w1i[:])
        gi = _mm(ar, w1i[:]) + _mm(ai, w1r[:])
        # Twiddle T[j2, k1] broadcast over the column chunk.
        gr = gr.reshape(n2, ct, n1)
        gi = gi.reshape(n2, ct, n1)
        hr = gr * tr[:][:, None, :] - gi * ti[:][:, None, :]
        hi = gr * ti[:][:, None, :] + gi * tr[:][:, None, :]
        # Stage 2 contracts j2: [j2, c, k1] -> [c, k1, j2] -> [c*k1, j2].
        hr = hr.transpose(1, 2, 0).reshape(ct * n1 // g2, g2 * n2)
        hi = hi.transpose(1, 2, 0).reshape(ct * n1 // g2, g2 * n2)
        zr = _mm(hr, w2r[:]) - _mm(hi, w2i[:])
        zi = _mm(hr, w2i[:]) + _mm(hi, w2r[:])
        # [c, k1, k2] -> [k2, k1, c]: leading flat (k2, k1) = output order.
        yr[:] = zr.reshape(ct, n1, n2).transpose(2, 1, 0)
        yi[:] = zi.reshape(ct, n1, n2).transpose(2, 1, 0)

    return kernel


def col_tile(n: int) -> int:
    """Column chunk per grid step for the strided kernel."""
    return _tile_rows("DFFT_PALLAS_TILE_STRIDED", 4 * 4 * n, 8)


@functools.partial(jax.jit, static_argnames=("n", "forward", "interpret"))
def _fft_strided_tiles(xr, xi, *, n: int, forward: bool, interpret: bool):
    """Length-n DFT over the LEADING axis of [n, cols] float32 re/im
    planes; cols must be a multiple of the tile size."""
    n1, n2 = split_for(n)
    cols = xr.shape[1]
    ct = min(col_tile(n), cols)
    grid = cols // ct
    g1, g2 = _packs(n1, n2, ct)

    w1, t, w2 = _tables_np(n, forward, g1, g2)
    consts = [jnp.asarray(p) for m in (w1, t, w2) for p in (m.real, m.imag)]
    vma = _vma(xr)
    if vma:
        consts = [pvary(c, tuple(vma)) for c in consts]

    lut_specs = [
        pl.BlockSpec(m.shape, lambda i: (0, 0), memory_space=pltpu.VMEM)
        for m in (w1, w1, t, t, w2, w2)
    ]
    x_spec = pl.BlockSpec((n1, n2, ct), lambda i: (0, 0, i),
                          memory_space=pltpu.VMEM)
    y_spec = pl.BlockSpec((n2, n1, ct), lambda i: (0, 0, i),
                          memory_space=pltpu.VMEM)

    yr, yi = pl.pallas_call(
        _make_kernel_strided(n1, n2, g1, g2),
        grid=(grid,),
        in_specs=lut_specs + [x_spec, x_spec],
        out_specs=(y_spec, y_spec),
        out_shape=(
            shape_dtype_struct((n2, n1, cols), jnp.float32, vma=vma),
            shape_dtype_struct((n2, n1, cols), jnp.float32, vma=vma),
        ),
        cost_estimate=pl.CostEstimate(
            flops=8 * cols * n * (g1 * n1 + g2 * n2),
            bytes_accessed=4 * cols * n * 4,
            transcendentals=0,
        ),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(*consts, xr.reshape(n1, n2, cols), xi.reshape(n1, n2, cols))
    return yr.reshape(n, cols), yi.reshape(n, cols)


def fft_axis0(x: jnp.ndarray, forward: bool = True,
              normalize: bool = True) -> jnp.ndarray:
    """C2C FFT over axis 0 of ``x`` via the strided kernel — no HBM
    transpose (callers gate on :func:`eligible` of ``x.shape[0]`` and
    complex64). Forward unnormalized, inverse scaled by 1/n
    (``normalize=False`` skips the inverse scale for composing stages)."""
    n = x.shape[0]
    rest = x.shape[1:]
    cols = math.prod(rest) if rest else 1
    x2 = x.reshape(n, cols)

    ct = min(col_tile(n), max(8, cols))
    pad = (-cols) % ct
    if pad:
        x2 = jnp.pad(x2, ((0, 0), (0, pad)))
    interpret = _interpret_mode()
    if interpret and _vma(x2):
        y = _four_step_ref(x2.T, n, forward).T
    else:
        yr, yi = _fft_strided_tiles(jnp.real(x2), jnp.imag(x2), n=n,
                                    forward=forward, interpret=interpret)
        y = lax.complex(yr, yi)
    if pad:
        y = y[:, :cols]
    if not forward and normalize:
        y = y * jnp.float32(1.0 / n)
    return y.reshape((n,) + rest)


@functools.lru_cache(maxsize=None)
def outer_split(n: int) -> tuple[int, int] | None:
    """Balanced divisor pair with BOTH factors kernel-eligible — the
    two-level plan for axes beyond one kernel's reach (the multi-upload
    regime of the reference's scheduler, ``templateFFT.cpp:4007-4100``:
    there >1 shared-memory passes, here >1 fused-kernel passes). Capped at
    n < 2^31 so the int32 twiddle phase stays exact; longer axes take the
    recursive matmul path."""
    if n >= 1 << 31:
        return None
    for d in range(int(math.isqrt(n)), 63, -1):
        if n % d == 0 and eligible(d) and eligible(n // d):
            return d, n // d
    return None


def _fft_last_big(x2: jnp.ndarray, n: int, forward: bool) -> jnp.ndarray:
    """Two-level four-step over [batch, n]: each DFT stage is a fused-kernel
    batched transform, the inter-stage twiddle/transposes run at the XLA
    level (exact int32 phase: i*j < n < 2^31)."""
    m1, m2 = outer_split(n)
    batch = x2.shape[0]
    a = x2.reshape(batch, m1, m2)
    # DFT over j1 via the vmapped strided kernel — in-VMEM reorders, no
    # HBM swapaxes round trip (the mirror path under shard_map on CPU
    # takes the explicit transposes instead).
    if _interpret_mode() and _vma(a):
        b = jnp.swapaxes(a, -1, -2).reshape(batch * m2, m1)
        b = _fft_eligible(b, m1, forward)
        b = jnp.swapaxes(b.reshape(batch, m2, m1), -1, -2)  # [batch, k1, j2]
    else:
        # Unnormalized stage: the caller applies the single 1/n at the end.
        b = jax.vmap(
            lambda v: fft_axis0(v, forward=forward, normalize=False))(a)
    i = jnp.arange(m1, dtype=jnp.int32)[:, None]
    j = jnp.arange(m2, dtype=jnp.int32)[None, :]
    phase = (i * j) % jnp.int32(n)
    sign = -2.0 if forward else 2.0
    ang = (sign * np.pi / n) * phase.astype(jnp.float32)
    b = b * lax.complex(jnp.cos(ang), jnp.sin(ang))
    c = _fft_eligible(b.reshape(batch * m1, m2), m2, forward)
    c = c.reshape(batch, m1, m2)
    # Output flat index k = k1 + m1*k2.
    return jnp.swapaxes(c, -1, -2).reshape(batch, n)


def _fft_eligible(x2: jnp.ndarray, n: int, forward: bool) -> jnp.ndarray:
    """Kernel-path transform of [batch, n] complex64 rows (n eligible),
    including the batch pad/crop discipline."""
    batch = x2.shape[0]
    bt = min(batch_tile(n), max(8, batch))
    pad = (-batch) % bt
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    interpret = _interpret_mode()
    if interpret and _vma(x2):
        y = _four_step_ref(x2, n, forward)
    else:
        yr, yi = _fft_tiles(jnp.real(x2), jnp.imag(x2), n=n, forward=forward,
                            interpret=interpret)
        y = lax.complex(yr, yi)
    return y[:batch] if pad else y


def _four_step_ref(x2: jnp.ndarray, n: int, forward: bool) -> jnp.ndarray:
    """jnp mirror of the kernel math (same LUTs, same contraction order and
    precision) for [batch, n] complex input. Used on the CPU test backend
    under shard_map, where the Pallas interpreter's grid loop cannot carry
    varying-axes types; numerics are identical to the kernel."""
    n1, n2 = split_for(n)
    w1, t, w2 = (jnp.asarray(m) for m in _tables_np(n, forward, 1, 1))
    a = x2.reshape(-1, n1, n2)
    from .dft_matmul import mm_precision

    g = jnp.einsum("bij,ik->bjk", a, w1, precision=mm_precision())
    h = g * t
    z = jnp.einsum("bjk,jl->bkl", h, w2, precision=mm_precision())
    return z.transpose(0, 2, 1).reshape(x2.shape)


def record_fallback(axis, reason: str) -> None:
    """Count one Pallas-eligibility fallback into the ``pallas_fallback``
    metrics series (axis + reason labels). Trace-time: the eligibility
    decision is static per compiled plan, so the counter ticks once per
    trace, not per execute — the observable is *which shapes route away
    from the kernel and why* (docs/OBSERVABILITY.md)."""
    from ..utils import metrics as _metrics

    _metrics.inc("pallas_fallback", axis=int(axis), reason=reason)


def fft_along_axis(x: jnp.ndarray, axis: int, forward: bool = True) -> jnp.ndarray:
    """C2C FFT along one axis via the fused Pallas kernel; falls back to the
    recursive MXU-matmul path for ineligible lengths/dtypes (counted in the
    ``pallas_fallback`` metrics series). Forward is unnormalized, inverse
    scaled by 1/n (numpy convention)."""
    from . import dft_matmul

    n = x.shape[axis]
    two_level = False
    if jnp.dtype(x.dtype) != jnp.complex64 or x.size == 0:
        record_fallback(axis, "dtype" if x.size else "empty")
        return dft_matmul.fft_along_axis(x, axis, forward=forward)
    if not eligible(n):
        if outer_split(n) is None:
            record_fallback(axis, "length")
            return dft_matmul.fft_along_axis(x, axis, forward=forward)
        two_level = True

    if axis % x.ndim == 0 and x.ndim > 1 and not two_level:
        # Leading-axis transform: the strided kernel reorders in VMEM,
        # skipping the two HBM moveaxis passes entirely.
        return fft_axis0(x, forward=forward)
    ax = axis % x.ndim
    if 0 < ax < x.ndim - 1 and not two_level:
        # Middle-axis transform: vmap the strided kernel over the leading
        # dims (the batching rule adds a grid dimension) — still no HBM
        # transpose.
        lead = math.prod(x.shape[:ax])
        shp = x.shape
        x3 = x.reshape((lead,) + x.shape[ax:ax + 1]
                       + (math.prod(x.shape[ax + 1:]),))
        y = jax.vmap(lambda v: fft_axis0(v, forward=forward))(x3)
        return y.reshape(shp)

    moved = axis not in (-1, x.ndim - 1)
    if moved:
        x = jnp.moveaxis(x, axis, -1)
    mshape = x.shape
    batch = math.prod(mshape[:-1]) if x.ndim > 1 else 1
    x = x.reshape(batch, n)

    if two_level:
        y = _fft_last_big(x, n, forward)
    else:
        y = _fft_eligible(x, n, forward)
    if not forward:
        y = y * jnp.float32(1.0 / n)
    y = y.reshape(mshape)
    if moved:
        y = jnp.moveaxis(y, -1, axis)
    return y


# --------------------------------------------------------------------------
# Plane-resident axis passes: the executor's path for complex64.
#
# The executor splits a complex64 operand into float32 (re, im) planes once,
# runs one pass per axis on the planes, and combines once. Each pass is one
# pallas_call that reads a tile of both planes from HBM, transforms it along
# the axis in VMEM and writes it back IN PLACE (input_output_aliases), so
# one plane pair is all the extra memory a transform holds.
#
# The axis is factored n = r * m with m = 128, the MXU width (decimation in
# time, j = j1 + r*j2, k = k2 + m*k1):
#
#     Y[j1, k2] = sum_j2 W_m[k2, j2] x[j1 + r*j2]      (MXU, 128-wide)
#     Y[j1, k2] *= w_n^{j1*k2}                         (VPU twiddle)
#     X[k2 + m*k1] = sum_j1 w_r^{j1*k1} Y[j1, k2]      (VPU radix-r)
#
# so the output rows come out in natural order in r contiguous blocks, and
# the input rows j1 + r*j2 are a sublane-strided load. Mosaic strides only a
# memref whose last dim is 128, so every kind of tile ends in 128 lanes:
#
#   "lane"    the last axis: [g*128, n] row tiles, transposed 128 rows at a
#             time into a [g, n, 128] VMEM scratch, transposed back on store;
#   "sublane" the second-minor axis: [g, n, 128] tiles;
#   "major"   any axis before it: [n, g, 128] tiles (the axis is a major
#             dim, each column a sublane of every row).
#
# Each complex product with the DFT matrix is three real matmuls (Gauss),
# at mm_precision() (HIGHEST: f32-exact multi-pass on the MXU).

#: The MXU contraction width of the plane passes' dense stage.
PLANE_WIDTH = 128

#: Largest radix the plane passes run on the VPU (n <= 8 * 128).
PLANE_MAX_RADIX = 8

#: Tile groups (of 128 rows or lanes) per grid step: 2 MiB per plane at
#: n = 512 (on a TPU v5e, 8 and 16 groups ran a 512^3 pass in the same
#: time).
_PLANE_GROUPS = 8


def plane_factor(n: int) -> tuple[int, int] | None:
    """(r, m) with n = r * m, m the MXU width, or None: the lengths the
    plane passes take."""
    r, rem = divmod(n, PLANE_WIDTH)
    if rem or not 1 <= r <= PLANE_MAX_RADIX:
        return None
    return r, PLANE_WIDTH


def _groups(extent: int, unit: int) -> int | None:
    """Largest g in 8, 4, 2, 1 with ``extent`` divisible by g * unit."""
    for g in (_PLANE_GROUPS, 4, 2, 1):
        if extent % (g * unit) == 0:
            return g
    return None


def _plane_layout(shape, axis: int):
    """How a plane pass tiles ``shape`` along ``axis``: (kind, g, view,
    block, grid, index_map), or None where it cannot. ``view`` is a
    reshape that keeps XLA's (8, 128) tiling of the last two dims, so it
    costs no copy."""
    nd = len(shape)
    axis %= nd
    n = shape[axis]
    if plane_factor(n) is None:
        return None
    lanes = shape[-1]
    if axis == nd - 1:
        rows = math.prod(shape[:-1])
        g = _groups(rows, PLANE_WIDTH)
        if g is None or (nd > 2 and shape[-2] % 8):
            return None
        return ("lane", g, (rows, n), (g * PLANE_WIDTH, n),
                (rows // (g * PLANE_WIDTH),), lambda i: (i, 0))
    if lanes % PLANE_WIDTH:
        return None
    lead = math.prod(shape[:axis])
    if axis == nd - 2:
        g = _groups(lead, 1)
        return ("sublane", g, (lead, n, lanes), (g, n, PLANE_WIDTH),
                (lead // g, lanes // PLANE_WIDTH), lambda a, c: (a, 0, c))
    mid = math.prod(shape[axis + 1:-1])
    if mid % _PLANE_GROUPS or (mid != shape[-2] and shape[-2] % 8):
        return None
    return ("major", _PLANE_GROUPS, (lead, n, mid, lanes),
            (1, n, _PLANE_GROUPS, PLANE_WIDTH),
            (lead, mid // _PLANE_GROUPS, lanes // PLANE_WIDTH),
            lambda a, b, c: (a, 0, b, c))


def plane_pass_eligible(shape, axis: int) -> bool:
    """True where :func:`fft_planes` transforms ``axis`` of a ``shape``
    plane pair with one in-place plane pass (other axes take the
    complex per-axis path)."""
    return _plane_layout(tuple(shape), axis) is not None


@functools.lru_cache(maxsize=None)
def _plane_tables(n: int, forward: bool):
    """float32 LUTs of one plane pass, host-exact in float64: the m-point
    DFT matrix W[k2, j2] as re, im and re + im (the Gauss product), and
    the twiddles w_n^{j1*k2} as [r, m, 1] columns. The inverse's 1/n is
    folded into W."""
    r, m = plane_factor(n)
    sign = -2j if forward else 2j
    k = np.arange(m)
    w = np.exp(sign * np.pi * np.outer(k, k) / m)
    if not forward:
        w = w / n
    t = np.exp(sign * np.pi * np.outer(np.arange(r), k) / n)[:, :, None]
    f32 = lambda a: np.ascontiguousarray(a, np.float32)  # noqa: E731
    return (f32(w.real), f32(w.imag), f32(w.real + w.imag),
            f32(t.real), f32(t.imag))


def _radix(ys, forward: bool):
    """The r-point DFT across ``ys`` = [(re, im)] * r on the VPU, with
    the trace-time coefficients 1, -1, +-i applied as sign flips."""
    r = len(ys)
    sign = -2j if forward else 2j
    outs = []
    for k1 in range(r):
        acc = None
        for j1, (yr, yi) in enumerate(ys):
            c = np.exp(sign * np.pi * ((j1 * k1) % r) / r)
            cr, ci = round(c.real, 12), round(c.imag, 12)
            if (cr, ci) == (1, 0):
                term = (yr, yi)
            elif (cr, ci) == (-1, 0):
                term = (-yr, -yi)
            elif (cr, ci) == (0, 1):
                term = (-yi, yr)
            elif (cr, ci) == (0, -1):
                term = (yi, -yr)
            else:
                cr, ci = np.float32(c.real), np.float32(c.imag)
                term = (yr * cr - yi * ci, yr * ci + yi * cr)
            acc = term if acc is None else (acc[0] + term[0],
                                            acc[1] + term[1])
        outs.append(acc)
    return outs


def _make_plane_kernel(n: int, forward: bool, kind: str, g: int,
                       interpret: bool):
    """One plane pass over a tile (see the section comment for the three
    ``kind`` layouts): per column group of 128 lanes, r sublane-strided
    loads of 128 rows, the Gauss matmuls and twiddle per j1, the radix-r
    combine, and r stores of 128 rows.

    A "major" tile [1, n, g, 128] is read as the [n*g, 128] rows it is
    in VMEM, so that row j of group c is row j*g + c and Mosaic strides
    it on the sublanes (indexed in 4D, Mosaic gathers each vreg with
    eight loads, rotates and selects, and stores it one sublane at a
    time). The interpreter cannot store through a reshaped ref, so there
    it indexes the 4D tile."""
    r, m = plane_factor(n)

    def rows(start, stride):
        return pl.ds(start, m, stride=stride) if stride > 1 else pl.ds(
            start, m)

    if kind == "major" and interpret:
        def get(ref, j1, c):
            return ref[0, rows(j1, r), c, :]

        def put(ref, k1, c, v):
            ref[0, k1 * m:(k1 + 1) * m, c, :] = v
    elif kind == "major":
        def get(ref, j1, c):
            return ref.reshape(n * g, m)[rows(j1 * g + c, r * g), :]

        def put(ref, k1, c, v):
            ref.reshape(n * g, m)[rows(k1 * m * g + c, g), :] = v
    elif kind == "sublane":
        def get(ref, j1, c):
            return ref[c, rows(j1, r), :]

        def put(ref, k1, c, v):
            ref[c, k1 * m:(k1 + 1) * m, :] = v
    else:
        def get(ref, j1, c):
            return ref[c, rows(j1, r), :]

        def put(ref, k1, c, v):
            ref[c * m:(c + 1) * m, k1 * m:(k1 + 1) * m] = v.T

    def kernel(wr, wi, ws, tr, ti, xr, xi, yr, yi, *scratch):
        src = (xr, xi)
        if kind == "lane":
            # Rows to sublanes: [128, n] -> [n, 128] per group.
            for c in range(g):
                for x, t in zip(src, scratch):
                    t[c] = x[c * m:(c + 1) * m, :].T
            src = scratch
        w_r, w_i, w_s = wr[...], wi[...], ws[...]

        def load(ref, j1):
            cols = [get(ref, j1, c) for c in range(g)]
            return cols[0] if g == 1 else jnp.concatenate(cols, axis=1)

        ys = []
        for j1 in range(r):
            ar, ai = load(src[0], j1), load(src[1], j1)
            p, q, s = _mm(w_r, ar), _mm(w_i, ai), _mm(w_s, ar + ai)
            br, bi = p - q, s - p - q
            if j1:
                cr, ci = tr[j1], ti[j1]
                br, bi = br * cr - bi * ci, br * ci + bi * cr
            ys.append((br, bi))
        for k1, (zr, zi) in enumerate(_radix(ys, forward)):
            for c in range(g):
                put(yr, k1, c, zr[:, c * m:(c + 1) * m])
                put(yi, k1, c, zi[:, c * m:(c + 1) * m])

    return kernel


def _plane_pass(re, im, axis: int, forward: bool):
    """One in-place plane pass of ``axis`` (:func:`plane_pass_eligible`);
    the outputs alias the plane operands, so XLA reuses their buffers
    wherever the caller has no other use for them."""
    shape = re.shape
    kind, g, view, block, grid, index_map = _plane_layout(shape, axis)
    n = shape[axis % len(shape)]
    vma = _vma(re)
    consts = [jnp.asarray(t) for t in _plane_tables(n, forward)]
    if vma:
        consts = [pvary(c, tuple(vma)) for c in consts]
    lut_specs = [pl.BlockSpec(c.shape, lambda *_, nd=c.ndim: (0,) * nd)
                 for c in consts]
    spec = pl.BlockSpec(block, index_map)
    scratch = ([pltpu.VMEM((g, n, PLANE_WIDTH), jnp.float32)] * 2
               if kind == "lane" else [])
    size = math.prod(shape)
    interpret = _interpret_mode()
    yr, yi = pl.pallas_call(
        _make_plane_kernel(n, forward, kind, g, interpret),
        grid=grid,
        in_specs=lut_specs + [spec, spec],
        out_specs=(spec, spec),
        out_shape=(shape_dtype_struct(view, jnp.float32, vma=vma),) * 2,
        scratch_shapes=scratch,
        input_output_aliases={len(consts): 0, len(consts) + 1: 1},
        cost_estimate=pl.CostEstimate(
            flops=6 * PLANE_WIDTH * size, bytes_accessed=16 * size,
            transcendentals=0),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel",) * len(grid),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=f"fft_plane_{kind}",
    )(*consts, re.reshape(view), im.reshape(view))
    return yr.reshape(shape), yi.reshape(shape)


def fft_planes(re, im, axes, forward: bool = True):
    """C2C FFT of the complex array ``re + i*im`` over ``axes``, float32
    planes in and out: the executor's complex64 path. Eligible axes take
    one in-place plane pass each; the others take
    :func:`fft_along_axis` (today's balanced-split kernels, or the
    counted matmul fallback), whose complex round trip XLA folds away.
    Forward unnormalized, inverse scaled by 1/n per axis."""
    interpret_vma = _interpret_mode() and bool(_vma(re))
    for ax in axes:
        if not interpret_vma and plane_pass_eligible(re.shape, ax):
            re, im = _plane_pass(re, im, ax, forward)
        else:
            y = fft_along_axis(lax.complex(re, im), ax, forward=forward)
            re, im = jnp.real(y), jnp.imag(y)
    return re, im
