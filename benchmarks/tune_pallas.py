#!/usr/bin/env python
"""Pallas kernel tuning sweep on live hardware.

The plan-time-autotune analog of the reference's scheduler exploring
shared-memory-sized axis splits (``templateFFT.cpp:3941-4100``): sweeps the
batch-tile size of the fused four-step kernel at a given axis length and
times it against the XLA FFT and the un-fused matmul path on the same
[batch, n] problem, then (optionally) the full 3D transform per executor.

Writes rows to ``benchmarks/csv/pallas_tune_<backend>.csv``. Run when a
real chip is attached; on CPU it measures the interpreter (only useful as
a smoke test with --quick).

Usage:
  python benchmarks/tune_pallas.py                 # n=512, batch=512^2
  python benchmarks/tune_pallas.py --n 1024 --tiles 64 128 256
  python benchmarks/tune_pallas.py --full3d 512
"""

from __future__ import annotations

import argparse
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def time_fn(f, *args, iters=10):
    """Shared timing methodology (utils.timing.time_fn_amortized) so tune
    numbers stay comparable with every other benchmark in the repo."""
    from distributedfft_tpu.utils.timing import time_fn_amortized

    return time_fn_amortized(f, *args, iters=iters, repeats=3)[0]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=512)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--tiles", type=int, nargs="*",
                    default=[64, 128, 256, 512])
    ap.add_argument("--full3d", type=int, default=None,
                    help="also time full 3D c2c at this cube size per executor")
    ap.add_argument("--strided", action="store_true",
                    help="also sweep the strided axis-0 kernel at --n")
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributedfft_tpu.utils.cache import enable_compile_cache

    enable_compile_cache()

    from distributedfft_tpu.ops import pallas_fft
    from distributedfft_tpu.utils.timing import max_rel_err, sync
    from distributedfft_tpu.utils.trace import CsvRecorder

    backend = jax.default_backend()
    here = os.path.dirname(os.path.abspath(__file__))
    rec = CsvRecorder(
        os.path.join(here, "csv", f"pallas_tune_{backend}.csv"),
        ("kind", "n", "batch", "tile", "seconds", "gflops", "max_err",
         "status"),
    )

    n = args.n
    batch = args.batch or (64 if args.quick else n * n)
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    xr = jax.random.normal(k1, (batch, n), jnp.float32)
    xi = jax.random.normal(k2, (batch, n), jnp.float32)
    x = jax.jit(jax.lax.complex)(xr, xi)
    sync(x)
    model = 5.0 * batch * n * math.log2(n)

    xla_fft = jax.jit(lambda a: jnp.fft.fft(a, axis=-1))
    try:
        t = time_fn(xla_fft, x)
        y_ref = xla_fft(x)
        sync(y_ref)
        rec.record("1d-xla", n, batch, "-", f"{t:.6f}",
                   f"{model / t / 1e9:.1f}", "0", "ok")
        print(f"xla fft [{batch},{n}]: {t*1e3:.3f} ms "
              f"({model/t/1e9:.1f} GFlops)", flush=True)
    except Exception as e:  # noqa: BLE001
        y_ref = None
        rec.record("1d-xla", n, batch, "-", "-", "-", "-",
                   f"error {type(e).__name__}")
        print(f"xla fft failed: {e}", file=sys.stderr, flush=True)

    from distributedfft_tpu.ops import dft_matmul

    mm = jax.jit(lambda a: dft_matmul.fft_along_axis(a, -1, forward=True))
    try:
        t = time_fn(mm, x)
        err = max_rel_err(mm(x), y_ref) if y_ref is not None else float("nan")
        rec.record("1d-matmul", n, batch, "-", f"{t:.6f}",
                   f"{model / t / 1e9:.1f}", f"{err:.3e}", "ok")
        print(f"matmul [{batch},{n}]: {t*1e3:.3f} ms "
              f"({model/t/1e9:.1f} GFlops) err={err:.2e}", flush=True)
    except Exception as e:  # noqa: BLE001
        rec.record("1d-matmul", n, batch, "-", "-", "-", "-",
                   f"error {type(e).__name__}")
        print(f"matmul failed: {e}", file=sys.stderr, flush=True)

    for tile in args.tiles:
        os.environ["DFFT_PALLAS_TILE"] = str(tile)
        pallas_fft._fft_tiles.clear_cache()
        try:
            pf = jax.jit(
                lambda a: pallas_fft.fft_along_axis(a, -1, forward=True))
            t = time_fn(pf, x)
            err = (max_rel_err(pf(x), y_ref)
                   if y_ref is not None else float("nan"))
            rec.record("1d-pallas", n, batch, tile, f"{t:.6f}",
                       f"{model / t / 1e9:.1f}", f"{err:.3e}", "ok")
            print(f"pallas tile={tile} [{batch},{n}]: {t*1e3:.3f} ms "
                  f"({model/t/1e9:.1f} GFlops) err={err:.2e}", flush=True)
        except Exception as e:  # noqa: BLE001
            msg = " ".join(str(e).split())[:140]
            rec.record("1d-pallas", n, batch, tile, "-", "-", "-",
                       f"error {msg}")
            print(f"pallas tile={tile} failed: {msg}", file=sys.stderr,
                  flush=True)
    os.environ.pop("DFFT_PALLAS_TILE", None)
    pallas_fft._fft_tiles.clear_cache()

    if args.strided:
        xs = jax.jit(lambda a: jnp.swapaxes(a, 0, 1))(x)  # [n, batch]
        sync(xs)
        xla0 = jax.jit(lambda a: jnp.fft.fft(a, axis=0))
        ys_ref = None
        try:
            t = time_fn(xla0, xs)
            ys_ref = xla0(xs)
            sync(ys_ref)
            rec.record("s-xla", n, batch, "-", f"{t:.6f}",
                       f"{model / t / 1e9:.1f}", "0", "ok")
            print(f"xla fft axis0 [{n},{batch}]: {t*1e3:.3f} ms "
                  f"({model/t/1e9:.1f} GFlops)", flush=True)
        except Exception as e:  # noqa: BLE001
            rec.record("s-xla", n, batch, "-", "-", "-", "-",
                       f"error {type(e).__name__}")
            print(f"xla axis0 failed: {e}", file=sys.stderr, flush=True)
        for tile in args.tiles:
            os.environ["DFFT_PALLAS_TILE_STRIDED"] = str(tile)
            pallas_fft._fft_strided_tiles.clear_cache()
            try:
                pf0 = jax.jit(lambda a: pallas_fft.fft_axis0(a, forward=True))
                t = time_fn(pf0, xs)
                err = (max_rel_err(pf0(xs), ys_ref)
                       if ys_ref is not None else float("nan"))
                rec.record("s-pallas", n, batch, tile, f"{t:.6f}",
                           f"{model / t / 1e9:.1f}", f"{err:.3e}", "ok")
                print(f"pallas strided ct={tile} [{n},{batch}]: "
                      f"{t*1e3:.3f} ms ({model/t/1e9:.1f} GFlops) "
                      f"err={err:.2e}", flush=True)
            except Exception as e:  # noqa: BLE001
                msg = " ".join(str(e).split())[:140]
                rec.record("s-pallas", n, batch, tile, "-", "-", "-",
                           f"error {msg}")
                print(f"pallas strided ct={tile} failed: {msg}",
                      file=sys.stderr, flush=True)
        os.environ.pop("DFFT_PALLAS_TILE_STRIDED", None)
        pallas_fft._fft_strided_tiles.clear_cache()

    if args.full3d:
        import distributedfft_tpu as dfft

        s = args.full3d
        shape = (s, s, s)
        model3 = 5.0 * s**3 * math.log2(s**3)
        for ex in ("xla", "pallas", "matmul"):
            try:
                plan = dfft.plan_dft_c2c_3d(shape, None, dtype=jnp.complex64,
                                            executor=ex)
                x3 = jax.jit(lambda: jax.lax.complex(
                    jax.random.normal(k1, shape, jnp.float32),
                    jax.random.normal(k2, shape, jnp.float32)))()
                sync(x3)
                t = time_fn(plan.fn, x3, iters=5)
                rec.record(f"3d-{ex}", s, 1, "-", f"{t:.6f}",
                           f"{model3 / t / 1e9:.1f}", "-", "ok")
                print(f"3d {ex} {shape}: {t*1e3:.2f} ms "
                      f"({model3/t/1e9:.1f} GFlops)", flush=True)
            except Exception as e:  # noqa: BLE001
                msg = " ".join(str(e).split())[:140]
                rec.record(f"3d-{ex}", s, 1, "-", "-", "-", "-",
                           f"error {msg}")
                print(f"3d {ex} failed: {msg}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
