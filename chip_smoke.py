#!/usr/bin/env python
"""Chip smoke: drive the library's main path once on a TPU, at the
reference's flagship size, through the entry points a user calls.

One process, one JSON line per phase (name, measured error, compile
seconds, counters), then the contract line
``{"ok": true, "device": {...}}`` as the LAST line of stdout.

Phases (one chip, the default):

- ``c2c``: ``plan_dft_c2c_3d((512,)*3, None)`` forward and BACKWARD, once
  per executor ``executor="auto"`` may pick (``api._AUTO_CANDIDATES``),
  each named explicitly. Input built on the host from ``--seed`` and
  ``device_put`` as complex64. Gate: relative L2 error of the forward
  output vs ``numpy.fft.fftn`` in complex128, and of the roundtrip,
  <= ``testing.TOLERANCE[complex64]``.
- ``r2c``: ``plan_dft_r2c_3d``/``plan_dft_c2r_3d`` vs ``numpy.fft.rfftn``
  /``irfftn`` under the same gate, once per auto candidate, each named,
  then with ``executor="auto"``; an earlier informational line prints
  the error of the ``DFFT_XLA_REAL=native`` route (not gated).
- ``serve``: 8 c2c requests of ``size/2`` cubed through
  ``CoalescingQueue(None, max_batch=4)`` -> two batched flushes, each
  result vs numpy; no retry, degraded rebuild or isolated failure.

``--chips 4`` runs only the 4-chip phases instead: the 512^3 c2c slab
on ``make_mesh(4)`` and the ``(2, 2)`` pencil, forward and backward vs
the same numpy reference, asserting the output shards sit on 4 distinct
devices with a quarter of the array each.

Every phase also requires zero ``pallas_fallback``/``fusion_fallback``
counts. Without a TPU the script exits non-zero and prints no result;
``--size N`` rehearses every phase's control flow on the CPU at a tiny
size and still exits non-zero; so does a TPU run at any size but 512.
The contract line's ``count`` is the number of chips the run used.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

import numpy as np

#: The reference's flagship cube extent: the only size whose run on the
#: TPU may print the contract line.
FLAGSHIP = 512

#: Counters every phase line reports; the first two must read zero.
COUNTERS = ("pallas_fallback", "fusion_fallback", "pallas_pack_disabled",
            "serving_flushes", "serving_retries", "serving_degraded",
            "serving_isolated_failures")


def rel_l2(got, ref) -> float:
    """||got - ref|| / ||ref|| in float64, chunked over axis 0 so a 1 GiB
    operand never needs a second full-size float64 temporary."""
    got = np.asarray(got)
    num = den = 0.0
    for i in range(0, got.shape[0], 32):
        r = np.asarray(ref[i:i + 32], dtype=np.result_type(ref, np.float64))
        d = got[i:i + 32] - r
        num += np.vdot(d, d).real
        den += np.vdot(r, r).real
    return float(np.sqrt(num / den))


def make_input(shape, seed: int, complex_: bool = True) -> np.ndarray:
    rng = np.random.default_rng(seed)
    re = rng.standard_normal(shape, dtype=np.float32)
    if not complex_:
        return re
    out = np.empty(shape, np.complex64)
    out.real = re
    out.imag = rng.standard_normal(shape, dtype=np.float32)
    return out


class Smoke:
    """Runs phases, prints one line each, remembers which failed."""

    def __init__(self, gate: float):
        import distributedfft_tpu as dfft

        self.dfft = dfft
        self.gate = gate
        self.failed: list[str] = []
        dfft.enable_metrics()

    def counters(self) -> dict:
        from distributedfft_tpu.utils.metrics import counter_total

        return {k: counter_total(k) for k in COUNTERS}

    def phase(self, name: str, body, gated: bool = True, **label) -> None:
        """Run ``body() -> dict`` of measurements; the phase passes when
        every ``*rel_l2`` value is within the gate and both fallback
        counters are zero. Any exception fails the phase (and the run)."""
        self.dfft.metrics_reset()
        line = {"phase": name, **label}
        try:
            line.update(body())
            line["counters"] = c = self.counters()
            errs = {k: v for k, v in line.items() if k.endswith("rel_l2")}
            problems = [f"{k}={v:.3e} > {self.gate}"
                        for k, v in errs.items() if not v <= self.gate]
            problems += [f"{k}={c[k]:g}" for k in
                         ("pallas_fallback", "fusion_fallback") if c[k]]
            problems += line.pop("problems", [])
            line["gate"] = self.gate
            ok = not (gated and problems)
            if problems:
                line["problems"] = problems
        except Exception as e:  # noqa: BLE001 — recorded, fails the run
            traceback.print_exc(file=sys.stderr)
            line["error"] = f"{type(e).__name__}: {e}"[:2000]
            ok = False
        line["passed"] = ok
        if not ok:
            self.failed.append(":".join([name, *map(str, label.values())]))
        print(json.dumps(line, default=str), flush=True)

    def pair(self, plan_fn, shape, mesh, **kw):
        """Forward and backward plans, each compiled eagerly and timed."""
        dfft = self.dfft
        plans, secs = [], {}
        for d, tag in ((dfft.FORWARD, "forward"), (dfft.BACKWARD, "backward")):
            t0 = time.perf_counter()
            p = plan_fn(shape, mesh, direction=d, **kw).compile()
            secs[tag] = round(time.perf_counter() - t0, 3)
            plans.append(p)
        return plans[0], plans[1], secs


def phases_one_chip(s: Smoke, n: int, seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from distributedfft_tpu.api import _AUTO_CANDIDATES
    from distributedfft_tpu.ops import pallas_fft

    dfft = s.dfft
    shape = (n,) * 3

    # --- c2c flagship, once per auto candidate -------------------------
    x_host = make_input(shape, seed)
    ref = np.fft.fftn(x_host.astype(np.complex128))
    for ex in _AUTO_CANDIDATES:
        def c2c(ex=ex):
            fwd, bwd, secs = s.pair(dfft.plan_dft_c2c_3d, shape, None,
                                    dtype=jnp.complex64, executor=ex)
            x = jax.device_put(x_host)  # complex host -> device transfer
            y = fwd(x)
            del x
            y_host = np.asarray(y)      # complex device -> host transfer
            r_host = np.asarray(bwd(y))
            del y
            out = {"rel_l2": rel_l2(y_host, ref),
                   "roundtrip_rel_l2": rel_l2(r_host, x_host),
                   "compile_s": secs}
            if ex.startswith("pallas"):
                out["pack_probe_disabled_packing"] = bool(
                    s.counters()["pallas_pack_disabled"])
                out["pallas_split"] = list(pallas_fft.split_for(n) or ())
            return out

        s.phase("c2c", c2c, executor=ex, shape=list(shape))
    del ref

    # --- r2c / c2r -----------------------------------------------------
    xr_host = make_input(shape, seed + 1, complex_=False)
    rref = np.fft.rfftn(xr_host.astype(np.float64))
    iref = np.fft.irfftn(rref, s=shape, axes=(0, 1, 2))

    def r2c(ex):
        fwd, bwd, secs = s.pair(dfft.plan_dft_r2c_3d, shape, None,
                                dtype=jnp.complex64, executor=ex)
        spec = np.asarray(fwd(jax.device_put(xr_host)))
        back = np.asarray(bwd(jax.device_put(rref.astype(np.complex64))))
        return {"picked": [fwd.executor, bwd.executor],
                "rel_l2": rel_l2(spec, rref), "c2r_rel_l2": rel_l2(back, iref),
                "roundtrip_rel_l2": rel_l2(
                    np.asarray(bwd(jax.device_put(spec))), xr_host),
                "compile_s": secs}

    os.environ["DFFT_XLA_REAL"] = "native"  # known lead; informational
    try:
        s.phase("r2c", lambda: r2c("xla"), gated=False, executor="xla",
                real_route="native", shape=list(shape))
    finally:
        del os.environ["DFFT_XLA_REAL"]
    from distributedfft_tpu.ops.executors import _xla_real_mode

    # Every executor the r2c/c2r planners' auto tournament may pick, each
    # named (the tournament times candidates, it never checks answers),
    # then auto itself.
    for ex in (*_AUTO_CANDIDATES, "auto"):
        s.phase("r2c", lambda ex=ex: r2c(ex), executor=ex,
                real_route=_xla_real_mode(), shape=list(shape))
    del rref, iref, xr_host

    # --- served requests: 8 x (n/2)^3 through the coalescing queue -----
    m = max(8, n // 2)

    def serve():
        q = dfft.CoalescingQueue(None, max_batch=4)
        xs = [make_input((m,) * 3, seed + 10 + i) for i in range(8)]
        hs = [q.submit(x) for x in xs]
        ys = [np.asarray(h.result()) for h in hs]
        q.close()
        errs = [rel_l2(y, np.fft.fftn(x.astype(np.complex128)))
                for y, x in zip(ys, xs)]
        c = s.counters()
        problems = [f"{k}={c[k]:g}" for k in (
            "serving_retries", "serving_degraded",
            "serving_isolated_failures") if c[k]]
        if c["serving_flushes"] != 2:
            problems.append(f"serving_flushes={c['serving_flushes']:g} != 2")
        return {"rel_l2": max(errs), "per_request_errors": errs,
                "problems": problems}

    s.phase("serve", serve, requests=8, max_batch=4, shape=[m] * 3)


def phases_four_chips(s: Smoke, n: int, seed: int) -> None:
    import jax
    import jax.numpy as jnp

    dfft = s.dfft
    shape = (n,) * 3
    x_host = make_input(shape, seed)
    ref = np.fft.fftn(x_host.astype(np.complex128))

    def placement(arr) -> dict:
        shards = arr.addressable_shards
        devs = {sh.device.id for sh in shards}
        quarter = all(sh.data.size * 4 == arr.size for sh in shards)
        return {"devices": sorted(devs), "shard_shape":
                list(shards[0].data.shape),
                "quarter_each": quarter and len(devs) == 4}

    for mesh_shape, name in (((4,), "slab"), ((2, 2), "pencil")):
        def dist(mesh_shape=mesh_shape):
            mesh = dfft.make_mesh(mesh_shape)
            fwd, bwd, secs = s.pair(dfft.plan_dft_c2c_3d, shape, mesh,
                                    dtype=jnp.complex64)
            x = jax.device_put(x_host, fwd.in_sharding)
            y = fwd(x)
            r = bwd(y)
            out = {"decomposition": fwd.decomposition,
                   "executor": [fwd.executor, bwd.executor],
                   "in_placement": placement(x),
                   "out_placement": placement(y),
                   "rel_l2": rel_l2(np.asarray(y), ref),
                   "roundtrip_rel_l2": rel_l2(np.asarray(r), x_host),
                   "compile_s": secs}
            out["problems"] = [f"{k} not on 4 distinct devices in quarters"
                               for k in ("in_placement", "out_placement")
                               if not out[k]["quarter_each"]]
            return out

        s.phase(name, dist, mesh=list(mesh_shape), shape=list(shape))


def contract_line(platform: str, kind: str, chips: int, n: int):
    """The last line of a passing run, or None where the run cannot pass:
    off the TPU, or at any size but the flagship's (the line carries no
    shape, so a toy run must not print it)."""
    if platform != "tpu" or n != FLAGSHIP:
        return None
    return json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": chips}})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1 (default): single-chip phases; 4: only the "
                         "slab and pencil phases on a 4-chip mesh")
    ap.add_argument("--size", type=int, default=None,
                    help=f"cube extent (default {FLAGSHIP}); any explicit "
                         f"size also allows a CPU rehearsal, and any run "
                         f"off the TPU or at any size but {FLAGSHIP} exits "
                         f"non-zero without the contract line")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    platform = jax.default_backend()
    if platform != "tpu" and args.size is None:
        print(f"chip_smoke: no TPU (JAX backend is {platform!r}); pass "
              f"--size N to rehearse on this backend", file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX sees {len(jax.devices())}", file=sys.stderr)
        return 2

    from distributedfft_tpu import testing
    from distributedfft_tpu.utils.cache import enable_compile_cache

    cache = enable_compile_cache()
    dev = jax.devices()[0]
    print(json.dumps({"phase": "setup", "platform": platform,
                      "device_kind": dev.device_kind,
                      "devices": len(jax.devices()),
                      "compile_cache": cache}), flush=True)
    s = Smoke(testing.tolerance(np.complex64))
    n = args.size or FLAGSHIP
    if args.chips == 4:
        phases_four_chips(s, n, args.seed)
    else:
        phases_one_chip(s, n, args.seed)

    if s.failed:
        print(f"chip_smoke: failed phases: {s.failed}", file=sys.stderr)
        return 1
    line = contract_line(dev.platform, dev.device_kind, args.chips, n)
    if line is None:
        print(f"chip_smoke: rehearsal ({platform}, {n}^3) passed; only a "
              f"TPU run at {FLAGSHIP}^3 can pass the smoke", file=sys.stderr)
        return 1
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
