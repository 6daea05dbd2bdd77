"""Fused stage+codec Pallas mega-kernels — the Pallas fusion tier's engine.

The reference's core speed trick is runtime kernel generation: one
specialized kernel per shape, staging a whole transform through on-chip
memory (``shaderGenFFT``, ``templateFFT.cpp:4699``). PR 12's
:mod:`.pallas_fft` brought that to single stages; this module fuses the
*stage boundary* the wire codec creates: in the unfused chain a compressed
exchange pays

    FFT kernel  -> write c64 block to HBM
    wire encode -> read c64 block, write wire bytes      (transport side)
    collective  -> wire bytes on the fabric
    wire decode -> read wire bytes, write c64 block      (transport side)
    FFT kernel  -> read c64 block from HBM

and this module's kernels collapse each side to ONE launch: the four-step
FFT (the exact :func:`.pallas_fft._four_step_pass` math) with the codec's
quantize/dequantize done in-register next to the butterfly, so the stage's
exchange-facing HBM stream is the *wire form*, never the intermediate c64
block. The stage-graph fusion pass (:func:`...stagegraph.plan_fusion`)
decides which stage pairs route here.

Kernel scope (everything else takes the pure-JAX mirror, values identical
to the unfused chain by construction):

- single transform axis, tiled on that same axis (the canonical fused
  pairs: every exchange's receiver FFT runs along the concat axis it
  decodes on, and the pencil sender FFT runs along the split axis it
  encodes on);
- complex64, kernel-eligible length (:func:`.pallas_fft.eligible`), tile
  count dividing the axis, and the whole local block VMEM-resident (one
  grid step — the per-(peer-tile, component-plane) amax reduction of the
  quantized codecs is a global reduction over the block, so the block
  must be in VMEM at once, :data:`_MAX_BLOCK_ELEMS`).

On the CPU test backend the mirrors also serve as the interpret-safe
shard_map path (the :func:`.pallas_fft._fft_eligible` discipline); the
kernel bodies themselves are exercised by the interpret-mode CI smoke
(``tests/test_a2q_fusion.py``) outside shard_map.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.compat import pvary, shape_dtype_struct, tpu_compiler_params
from . import pallas_fft
from .pallas_fft import (
    _VMEM_BUDGET,
    _VMEM_LIMIT,
    _interpret_mode,
    _tables_np,
    _vma,
    eligible,
    split_for,
)

#: Largest local block (float32 elements) a fused kernel holds in VMEM:
#: one block copy must fit the per-tile VMEM budget, since the kernel's
#: working set is about a dozen live copies (the measured stack model
#: behind ``pallas_fft._VMEM_BUDGET``).
_MAX_BLOCK_ELEMS = _VMEM_BUDGET // 4

#: Quantized codecs the in-kernel pack supports: name -> (signed levels,
#: mantissa dtype). ``bf16`` is the cast-only codec (no amax reduction).
_Q_CODECS = {"int8": (127.0, jnp.int8), "split": (32767.0, jnp.int16)}

#: Wire codecs with an in-kernel pack/unpack.
FUSABLE_CODECS = ("bf16",) + tuple(_Q_CODECS)


def record_fusion_fallback(site, reason: str) -> None:
    """Count one fusion fallback into the ``fusion_fallback`` metrics
    series (site + reason labels). Trace-time, like
    :func:`.pallas_fft.record_fallback`: the decision is static per
    compiled plan; the observable is which sites route away from the
    fused path and why (docs/OBSERVABILITY.md)."""
    from ..utils import metrics as _metrics

    _metrics.inc("fusion_fallback", site=str(site), reason=str(reason))


def kernel_ineligible(shape, fft_axis: int, tile_axis: int, tiles: int,
                      dtype, wire_dtype: str) -> str | None:
    """Why the fused kernel cannot run this site, or None if it can.
    Pure shape/dtype algebra (no backend query) — shared by the trace
    and by the tests pinning the fallback taxonomy."""
    if wire_dtype not in FUSABLE_CODECS:
        return "codec"
    if jnp.dtype(dtype) != jnp.complex64:
        return "dtype"
    elems = math.prod(int(s) for s in shape)
    if elems == 0:
        return "empty"
    ndim = len(shape)
    fa, ta = fft_axis % ndim, tile_axis % ndim
    if fa != ta:
        return "tile_axis"
    n = int(shape[fa])
    if not eligible(n):
        return "length"
    if tiles < 1 or n % tiles:
        return "uneven_tiles"
    if elems > _MAX_BLOCK_ELEMS:
        return "vmem"
    return None


def _pow2_step_block(amax, levels: float):
    """In-kernel power-of-two step (the :func:`...parallel.exchange`
    ``_pow2_step`` math at any level count): exact decode products,
    exact encode/decode idempotence, and sidecars bit-identical to the
    mirror codecs' (``exchange.exact_pow2`` bit-construction — XLA's
    ``exp2`` can be 1 ulp off a true power of two)."""
    safe = jnp.where(amax > 0.0, amax, jnp.float32(levels))
    k = jnp.clip(jnp.ceil(jnp.log2(safe / levels)),
                 -126.0, 127.0).astype(jnp.int32)
    step = lax.bitcast_convert_type((k + 127) << 23, jnp.float32)
    return jnp.where(amax > 0.0, step, jnp.float32(1.0))


def _make_encode_kernel(R: int, n: int, n1: int, n2: int, tiles: int,
                        codec: str, forward: bool):
    """FFT + wire-encode mega-kernel body over one [R, n] block: four-step
    transform of every row, then the codec pack — bf16 cast, or the
    per-(tile segment, component plane) pow2 quantization — all in VMEM.
    Tile segments partition the TRANSFORMED axis (the fused pairs always
    tile the exchange axis they transform)."""
    seg = n // tiles
    inv = None if forward else float(1.0 / n)

    def _transform(xr, xi, w1r, w1i, tr, ti, w2r, w2i):
        zr, zi = pallas_fft._four_step_pass(
            xr.reshape(R, n1, n2), xi.reshape(R, n1, n2),
            w1r, w1i, tr, ti, w2r, w2i)
        yr, yi = zr.reshape(R, n), zi.reshape(R, n)
        if inv is not None:
            yr, yi = yr * inv, yi * inv
        return yr, yi

    if codec == "bf16":
        def kernel(w1r, w1i, tr, ti, w2r, w2i, xr, xi, q):
            yr, yi = _transform(xr[:], xi[:], w1r[:], w1i[:], tr[:],
                                ti[:], w2r[:], w2i[:])
            q[:] = jnp.stack([yr, yi], axis=-1).astype(jnp.bfloat16)

        return kernel

    levels, qdt = _Q_CODECS[codec]

    def kernel(w1r, w1i, tr, ti, w2r, w2i, xr, xi, q, s):
        yr, yi = _transform(xr[:], xi[:], w1r[:], w1i[:], tr[:],
                            ti[:], w2r[:], w2i[:])
        # Per-tile-segment amax over the whole block (tile leading so the
        # reduction runs over one contiguous [R*seg] extent per tile).
        tr_ = yr.reshape(R, tiles, seg).transpose(1, 0, 2)
        ti_ = yi.reshape(R, tiles, seg).transpose(1, 0, 2)
        amr = jnp.max(jnp.abs(tr_.reshape(tiles, R * seg)), axis=1,
                      keepdims=True)
        ami = jnp.max(jnp.abs(ti_.reshape(tiles, R * seg)), axis=1,
                      keepdims=True)
        sr = _pow2_step_block(amr, levels)
        si = _pow2_step_block(ami, levels)
        qr = jnp.clip(jnp.round(tr_ / sr.reshape(tiles, 1, 1)),
                      -levels, levels).astype(qdt)
        qi = jnp.clip(jnp.round(ti_ / si.reshape(tiles, 1, 1)),
                      -levels, levels).astype(qdt)
        q[:] = jnp.stack([qr.transpose(1, 0, 2).reshape(R, n),
                          qi.transpose(1, 0, 2).reshape(R, n)], axis=-1)
        s[:] = jnp.concatenate([sr, si], axis=1)

    return kernel


def _make_decode_kernel(R: int, n: int, n1: int, n2: int, tiles: int,
                        codec: str, forward: bool):
    """Wire-decode + FFT mega-kernel body over one [R, n, 2] wire block:
    the codec unpack (bf16 cast, or mantissa * pow2-step — exact), then
    the four-step transform of every row, all in VMEM."""
    seg = n // tiles
    inv = None if forward else float(1.0 / n)

    def _finish(vr, vi, w1r, w1i, tr, ti, w2r, w2i, yr, yi):
        zr, zi = pallas_fft._four_step_pass(
            vr.reshape(R, n1, n2), vi.reshape(R, n1, n2),
            w1r, w1i, tr, ti, w2r, w2i)
        zr, zi = zr.reshape(R, n), zi.reshape(R, n)
        if inv is not None:
            zr, zi = zr * inv, zi * inv
        yr[:] = zr
        yi[:] = zi

    if codec == "bf16":
        def kernel(w1r, w1i, tr, ti, w2r, w2i, q, yr, yi):
            qv = q[:]
            _finish(qv[..., 0].astype(jnp.float32),
                    qv[..., 1].astype(jnp.float32),
                    w1r[:], w1i[:], tr[:], ti[:], w2r[:], w2i[:], yr, yi)

        return kernel

    def kernel(w1r, w1i, tr, ti, w2r, w2i, q, s, yr, yi):
        qv = q[:]
        sv = s[:]  # [tiles, 2] pow2 steps
        vr = (qv[..., 0].astype(jnp.float32).reshape(R, tiles, seg)
              * sv[:, 0].reshape(1, tiles, 1)).reshape(R, n)
        vi = (qv[..., 1].astype(jnp.float32).reshape(R, tiles, seg)
              * sv[:, 1].reshape(1, tiles, 1)).reshape(R, n)
        _finish(vr, vi, w1r[:], w1i[:], tr[:], ti[:], w2r[:], w2i[:],
                yr, yi)

    return kernel


def _luts(n: int, forward: bool, vma):
    w1, t, w2 = _tables_np(n, forward, 1, 1)
    consts = [jnp.asarray(p) for m in (w1, t, w2)
              for p in (m.real, m.imag)]
    if vma:
        consts = [pvary(c, tuple(vma)) for c in consts]
    specs = [
        pl.BlockSpec(m.shape, lambda i: (0, 0), memory_space=pltpu.VMEM)
        for m in (w1, w1, t, t, w2, w2)
    ]
    return consts, specs


@functools.partial(jax.jit, static_argnames=(
    "n", "forward", "tiles", "codec", "interpret"))
def _encode_tiles(xr, xi, *, n: int, forward: bool, tiles: int,
                  codec: str, interpret: bool):
    """One fused FFT+encode launch over the whole [R, n] block (single
    grid step — the per-tile amax is a block-global reduction)."""
    R = xr.shape[0]
    n1, n2 = split_for(n)
    vma = _vma(xr)
    consts, lut_specs = _luts(n, forward, vma)
    x_spec = pl.BlockSpec((R, n1, n2), lambda i: (0, 0, 0),
                          memory_space=pltpu.VMEM)
    q_spec = pl.BlockSpec((R, n, 2), lambda i: (0, 0, 0),
                          memory_space=pltpu.VMEM)
    if codec == "bf16":
        out_specs = q_spec
        out_shape = shape_dtype_struct((R, n, 2), jnp.bfloat16, vma=vma)
    else:
        _, qdt = _Q_CODECS[codec]
        out_specs = (q_spec,
                     pl.BlockSpec((tiles, 2), lambda i: (0, 0),
                                  memory_space=pltpu.VMEM))
        out_shape = (shape_dtype_struct((R, n, 2), qdt, vma=vma),
                     shape_dtype_struct((tiles, 2), jnp.float32, vma=vma))
    out = pl.pallas_call(
        _make_encode_kernel(R, n, n1, n2, tiles, codec, forward),
        grid=(1,),
        in_specs=lut_specs + [x_spec, x_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        cost_estimate=pl.CostEstimate(
            flops=8 * R * n * (n1 + n2),
            bytes_accessed=2 * R * n * 4 + R * n * 2 * 2,
            transcendentals=0,
        ),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(*consts, xr.reshape(R, n1, n2), xi.reshape(R, n1, n2))
    return out if isinstance(out, tuple) else (out,)


@functools.partial(jax.jit, static_argnames=(
    "n", "forward", "tiles", "codec", "interpret"))
def _decode_tiles(q, s, *, n: int, forward: bool, tiles: int, codec: str,
                  interpret: bool):
    """One fused decode+FFT launch over the whole [R, n, 2] wire block."""
    R = q.shape[0]
    n1, n2 = split_for(n)
    vma = _vma(q)
    consts, lut_specs = _luts(n, forward, vma)
    q_spec = pl.BlockSpec((R, n, 2), lambda i: (0, 0, 0),
                          memory_space=pltpu.VMEM)
    in_specs = lut_specs + [q_spec]
    operands = [q]
    if codec != "bf16":
        in_specs.append(pl.BlockSpec((tiles, 2), lambda i: (0, 0),
                                     memory_space=pltpu.VMEM))
        operands.append(s)
    y_spec = pl.BlockSpec((R, n), lambda i: (0, 0),
                          memory_space=pltpu.VMEM)
    yr, yi = pl.pallas_call(
        _make_decode_kernel(R, n, n1, n2, tiles, codec, forward),
        grid=(1,),
        in_specs=in_specs,
        out_specs=(y_spec, y_spec),
        out_shape=(
            shape_dtype_struct((R, n), jnp.float32, vma=vma),
            shape_dtype_struct((R, n), jnp.float32, vma=vma),
        ),
        cost_estimate=pl.CostEstimate(
            flops=8 * R * n * (n1 + n2),
            bytes_accessed=2 * R * n * 4 + R * n * 2 * 2,
            transcendentals=0,
        ),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
    )(*consts, *operands)
    return yr, yi


def fused_fft_encode(x: jnp.ndarray, *, fft_axis: int, forward: bool,
                     tile_axis: int, tiles: int, wire_dtype: str,
                     site: str = "fft_encode") -> tuple:
    """Stage FFT + wire encode as ONE kernel launch where eligible.

    Returns exactly what ``wire_codec(wire_dtype).encode(ex(x), ...)``
    returns — the tuple of wire parts, payload first — so the caller
    ships them through the transport unchanged. Ineligible shapes and
    the CPU shard_map interpreter take the pure-JAX mirror (the unfused
    executor + codec — bit-identical to the unfused chain); kernel
    fallbacks are counted in the ``fusion_fallback`` series."""
    from ..parallel.exchange import wire_codec

    codec = wire_codec(wire_dtype)
    reason = kernel_ineligible(x.shape, fft_axis, tile_axis, tiles,
                               x.dtype, wire_dtype)
    interpret = _interpret_mode()
    if reason is not None:
        record_fusion_fallback(site, reason)
    if reason is not None or (interpret and _vma(x)):
        y = pallas_fft.fft_along_axis(x, fft_axis, forward=forward)
        return codec.encode(y, tile_axis=tile_axis, tiles=tiles)
    fa = fft_axis % x.ndim
    xm = jnp.moveaxis(x, fa, -1) if fa != x.ndim - 1 else x
    mshape = xm.shape
    n = mshape[-1]
    R = math.prod(mshape[:-1]) if xm.ndim > 1 else 1
    out = _encode_tiles(
        jnp.real(xm).reshape(R, n).astype(jnp.float32),
        jnp.imag(xm).reshape(R, n).astype(jnp.float32),
        n=n, forward=forward, tiles=tiles, codec=wire_dtype,
        interpret=interpret)
    q = out[0].reshape(mshape + (2,))
    if fa != x.ndim - 1:
        q = jnp.moveaxis(q, -2, fa)
    if wire_dtype == "bf16":
        return (q,)
    bshape = [1] * (x.ndim + 1)
    bshape[fa] = tiles
    bshape[-1] = 2
    return (q, out[1].reshape(bshape))


def fused_decode_fft(parts: tuple, dtype, *, fft_axis: int, forward: bool,
                     tile_axis: int, tiles: int, wire_dtype: str,
                     site: str = "decode_fft") -> jnp.ndarray:
    """Wire decode + stage FFT as ONE kernel launch where eligible —
    the receiver-side twin of :func:`fused_fft_encode`. ``parts`` is the
    post-collective wire tuple; ``tile_axis`` names where the peer tiles
    sit NOW (the concat axis). Same mirror/fallback discipline."""
    from ..parallel.exchange import wire_codec

    codec = wire_codec(wire_dtype)
    payload = parts[0]
    shape = payload.shape[:-1]
    reason = kernel_ineligible(shape, fft_axis, tile_axis, tiles, dtype,
                               wire_dtype)
    interpret = _interpret_mode()
    if reason is not None:
        record_fusion_fallback(site, reason)
    if reason is not None or (interpret and _vma(payload)):
        y = codec.decode(parts, dtype, tile_axis=tile_axis, tiles=tiles)
        return pallas_fft.fft_along_axis(y, fft_axis, forward=forward)
    ndim = len(shape)
    fa = fft_axis % ndim
    qm = jnp.moveaxis(payload, fa, -2) if fa != ndim - 1 else payload
    mshape = qm.shape[:-1]
    n = mshape[-1]
    R = math.prod(mshape[:-1]) if len(mshape) > 1 else 1
    scales = (parts[1].reshape(tiles, 2) if wire_dtype != "bf16"
              else None)
    yr, yi = _decode_tiles(qm.reshape(R, n, 2), scales, n=n,
                           forward=forward, tiles=tiles, codec=wire_dtype,
                           interpret=interpret)
    y = lax.complex(yr, yi).astype(dtype).reshape(mshape)
    if fa != ndim - 1:
        y = jnp.moveaxis(y, -1, fa)
    return y
