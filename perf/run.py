#!/usr/bin/env python3
"""One run of one benchmark cell, on the chips of this machine.

    python3 perf/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration and a traffic mix (``BENCHMARK.json``);
the harness finds each by name: ``<configs entry>.file`` for the
configuration, ``perf/traffic/<traffic>.json`` for the mix,
``perf/transforms/<transform>.py`` for the planner, seeded input and
plain reference of the configuration's transform, and
``perf/metrics/<metric>.py`` for each metric's reader. Adding any of
them is adding a file and an entry.

A run builds the cell's plans through the program's own planners (plan
build and compile timed as ``plan_s``), makes its input on the device
from ``--seed``, warms up, then for ``--seconds`` drives the plans as a
user does, with a bounded number of transforms in flight, and ends the
window when every one of them is ready. Set-up (``setup_s``) is
everything from the start of this process to the first timed transform.
With ``--trace 1`` the window runs under the JAX profiler and the line
carries the per-layer metrics instead of the end-to-end ones.

After the window the run is checked: a sample of the window's outputs,
one drawn from the seed and the last, must sit in the sharding the plan
documents and lie within the configuration's tolerance of a float64
reference of the same input, computed on the host once the device state
is freed. Each number compared is printed with its limit, last on
standard error and last in the result line (``checks``).

Off a TPU, or with fewer chips than the cell asks for, the run prints no
result and exits 2. It never falls back to the CPU.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import concurrent.futures  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
#: JAX's persistent compile cache: one fixed directory in the checkout
#: (the path is part of the cache key) that only the benchmark writes,
#: whatever the machine sets.
CACHE = ROOT / ".perf_jax_cache"


@dataclass
class Cell:
    """A cell as ``BENCHMARK.json`` and its files define it."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    root: Path


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """Resolve ``name`` through ``root/BENCHMARK.json`` to its files."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    w = cells[name]
    entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = json.loads((root / entry["file"]).read_text())
    traffic = json.loads(
        (root / "perf" / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(name, int(w["chips"]), config, traffic,
                [m for m in bench["end_to_end"] if _applies(m, name)],
                [m for m in bench["per_layer"] if _applies(m, name)], root)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"perf_{path.parent.name}_{path.stem}".replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Run:
    """What a metric's reader reads (``perf/metrics/<name>.py``,
    ``read(run) -> float | None``)."""

    steps: int                # traffic steps completed in the window
    window_s: float           # host clock, first dispatch to last ready
    setup_s: float
    plan_s: float
    flops_per_step: float     # nominal, all chips together
    min_step_s: float         # roofline of one step on one chip
    memory_peak_bytes: int | None
    trace: object = None      # tracekit.Trace of the window, --trace 1


def _chunks(n: int, pool, fn):
    """``fn(lo, hi)`` over blocks of axis 0, on the pool's threads (NumPy
    releases the GIL in the copies and BLAS calls these make)."""
    step = max(1, n // 64)
    return list(pool.map(lambda lo: fn(lo, min(n, lo + step)),
                         range(0, n, step)))


def rel_l2(got, ref, pool) -> float:
    """||got - ref|| / ||ref|| in float64, over blocks of axis 0 so that
    no full-size temporary is made."""
    import numpy as np

    def part(lo, hi):
        d = got[lo:hi] - ref[lo:hi]
        r = ref[lo:hi]
        return np.vdot(d, d).real, np.vdot(r, r).real

    parts = _chunks(got.shape[0], pool, part)
    num, den = sum(p[0] for p in parts), sum(p[1] for p in parts)
    return float(math.sqrt(num / den)) if den > 0 else math.inf


def fetch(a, dtype, pool):
    """A device array on the host as ``dtype``. A complex array comes as
    its real and imaginary float32 parts: from the v5e a complex64
    transfer of 1 GiB takes 5.8 s and the two parts 0.5 s together (my
    chip run, PR 22)."""
    import jax.numpy as jnp
    import numpy as np

    if not jnp.iscomplexobj(a):
        return np.asarray(a, dtype)
    re, im = np.asarray(jnp.real(a)), np.asarray(jnp.imag(a))
    out = np.empty(a.shape, dtype)

    def part(lo, hi):
        out[lo:hi].real = re[lo:hi]
        out[lo:hi].imag = im[lo:hi]

    _chunks(a.shape[0], pool, part)
    return out


def _seed_words(seed: int):
    """Two 32-bit words from a seed of any size."""
    import numpy as np

    return np.random.SeedSequence(seed).generate_state(2)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool = False, *,
             overrides: dict | None = None, trace_dir: str | None = None,
             t_start: float = T_PROCESS) -> dict:
    """Run ``cell`` once on the devices JAX sees; return the result
    line's object. ``overrides`` replace planner arguments of the
    configuration (the control runs); nothing else differs from a
    benchmark run."""
    t_enter = time.perf_counter()
    import jax
    import jax.numpy as jnp
    import numpy as np

    import distributedfft_tpu as dfft

    cfg, traffic = cell.config, cell.traffic
    kind = load_module(cell.root / "perf" / "transforms"
                       / f"{cfg['transform']}.py")
    devices = jax.devices()[:cell.chips]
    chain = traffic["chain"]

    # The plans are built, then compiled by their first call on the
    # input, as a user's are. ``Plan3D.compile()`` is not used: it runs a
    # zero-filled array made whole on one chip first, which a grid
    # larger than one chip does not fit (RESOURCE_EXHAUSTED at 1024^3 on
    # four chips, my chip run, PR 22).
    t = time.perf_counter()
    mesh = dfft.make_mesh(tuple(cfg["mesh"])) if cfg["mesh"] else None
    kwargs = {**cfg.get("planner_kwargs", {}), **(overrides or {})}
    plans = {s: getattr(dfft, kind.PLANNER)(
                 tuple(cfg["shape"]), mesh, direction=kind.DIRECTION[s],
                 dtype=np.dtype(cfg["dtype"]), **kwargs)
             for s in dict.fromkeys(chain)}
    t_built = time.perf_counter()
    first, final = plans[chain[0]], plans[chain[-1]]

    words = _seed_words(seed)
    make = jax.jit(lambda k: kind.make_input(k, first.in_shape),
                   **({"out_shardings": first.in_sharding}
                      if first.in_sharding is not None else {}))
    x = make(words).block_until_ready()
    t_input = time.perf_counter()

    def step(v):
        for s in chain:
            v = plans[s](v)
        return v

    step(x).block_until_ready()
    t_compiled = time.perf_counter()
    plan_s = (t_built - t) + (t_compiled - t_input)
    for _ in range(traffic["warmup"]):
        step(x).block_until_ready()

    sample_at = int(np.random.default_rng(seed).integers(
        traffic["sample_within"]))
    depth = traffic["in_flight"]
    span = jax.profiler.TraceAnnotation if trace else (
        lambda _: contextlib.nullcontext())
    tdir = tempfile.mkdtemp(prefix="perf-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(tdir)
    with span("window"):
        t0 = time.perf_counter()
        setup_s = t0 - t_start
        deadline = t0 + seconds
        inflight = collections.deque()
        kept, steps = None, 0
        while True:
            if len(inflight) >= depth:
                with span("sync"):
                    inflight.popleft().block_until_ready()
            with span("dispatch"):
                y = step(x)
            if steps == sample_at:
                kept = y
            inflight.append(y)
            steps += 1
            if time.perf_counter() >= deadline:
                break
        with span("sync"):
            for y in inflight:
                y.block_until_ready()
        t1 = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    print(f"setup took {setup_s:.3f} s: process and runtime start "
          f"{t_enter - t_start:.3f}, program import {t - t_enter:.3f}, "
          f"plan build {t_built - t:.3f}, input {t_input - t_built:.3f}, "
          f"first call {t_compiled - t_input:.3f}, warm-up "
          f"{t0 - t_compiled:.3f}", file=sys.stderr)

    stats = [d.memory_stats() for d in devices]
    peak = (max(s["peak_bytes_in_use"] for s in stats)
            if all(s and "peak_bytes_in_use" in s for s in stats) else None)

    # --- the check: outputs of the timed plans, at the timed size -------
    outs = {"sample": kept if kept is not None else inflight[-1],
            "last": inflight[-1]}
    want = final.out_sharding or jax.sharding.SingleDeviceSharding(devices[0])
    misplaced = sum(
        not (o.shape == tuple(final.out_shape)
             and o.dtype == np.dtype(final.out_dtype)
             and o.sharding.is_equivalent_to(want, o.ndim))
        for o in outs.values())
    t_check = time.perf_counter()
    pool = concurrent.futures.ThreadPoolExecutor(os.cpu_count())
    # The loop transforms one input, so a sound program answers the same
    # bits each time: where the last answer equals the sample on the
    # device, one transfer serves both comparisons (at 1024^3 a transfer
    # takes 14 s, my chip run, PR 22).
    same = bool(jnp.array_equal(outs["sample"], outs["last"]))
    host = {"sample": fetch(outs["sample"], final.out_dtype, pool)}
    host["last"] = (host["sample"] if same
                    else fetch(outs["last"], final.out_dtype, pool))
    t_fetch = time.perf_counter()
    del x, y, kept, inflight, outs
    x_host = fetch(make(words), np.result_type(first.in_dtype, np.float64),
                   pool)
    t_again = time.perf_counter()
    for s in chain:
        x_host = kind.reference(s, x_host)
    t_ref = time.perf_counter()
    tol = float(cfg["tolerance"])
    err = {"sample": rel_l2(host["sample"], x_host, pool)}
    err["last"] = err["sample"] if same else rel_l2(host["last"], x_host, pool)
    checks = {f"rel_l2_{k}": {"value": e, "limit": tol} for k, e in err.items()}
    pool.shutdown()
    checks["misplaced_outputs"] = {"value": misplaced, "limit": 0}
    del host, x_host
    check_s = time.perf_counter() - t_check
    print(f"check took {check_s:.3f} s (last answer equal to the sample: "
          f"{same}): outputs to host "
          f"{t_fetch - t_check:.3f}, input again {t_again - t_fetch:.3f}, "
          f"reference {t_ref - t_again:.3f}, comparison "
          f"{t_check + check_s - t_ref:.3f}", file=sys.stderr)
    wrong = [k for k, c in checks.items() if not c["value"] <= c["limit"]]

    roof = load_module(cell.root / "perf" / "roofline.py")
    dev0 = devices[0]
    flops = sum(roof.transform_flops(plans[s].shape, kind.REAL)
                for s in chain)
    hbm = sum(roof.min_hbm_bytes(plans[s].in_shape, plans[s].in_dtype,
                                 plans[s].out_shape, plans[s].out_dtype)
              for s in chain)
    run = Run(steps, t1 - t0, setup_s, plan_s, flops,
              roof.min_seconds(flops / cell.chips, hbm / cell.chips,
                               roof.peak(dev0.device_kind))
              if dev0.platform == "tpu" else math.nan,
              peak)
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": cell.chips, "memory_peak_bytes": peak}
    line = {"correct": not wrong, "attempted": steps, "failed": len(wrong)}
    if trace:
        tk = load_module(cell.root / "perf" / "tracekit.py")
        pb = next(Path(tdir).rglob("*.xplane.pb"))
        if trace_dir:
            shutil.copy(pb, Path(trace_dir) / f"{cell.name}.xplane.pb")
        run.trace = tk.load_xplane(pb)
        shutil.rmtree(tdir, ignore_errors=True)
        device["busy_s"] = sum(run.trace.busy_s(d) for d in run.trace.ops) / max(
            1, len(run.trace.ops))
        device["window_s"] = run.trace.window_s
    metrics = {}
    for m in cell.per_layer if trace else cell.end_to_end:
        reader = load_module(cell.root / "perf" / "metrics" / f"{m['name']}.py")
        v = reader.read(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    line.update(metrics=metrics, device=device)
    if trace:
        line["breakdown"] = tk.breakdown(run.trace, cell.chips)
    line["check_s"] = check_s
    line["checks"] = checks
    return line


def prepare(chips: int) -> bool:
    """Set the process up as every benchmark run is set up; False where
    JAX finds no TPU or fewer than ``chips`` chips. The cell is what its
    files say: no program knob from the caller's environment, and
    compiled programs only from the checkout's own cache."""
    for k in [k for k in os.environ if k.startswith("DFFT_")]:
        del os.environ[k]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    os.environ["TPU_LOG_DIR"] = "disabled"
    sys.path.insert(0, str(ROOT))

    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # No LRU eviction: it needs an access-time file beside every entry,
    # and one entry without it (written with eviction off) makes every
    # later write fail, so that no run ever hits (my chip run, PR 22).
    jax.config.update("jax_compilation_cache_max_size", -1)
    backend = jax.default_backend()
    if backend != "tpu" or len(jax.devices()) < chips:
        print(f"run.py: needs {chips} TPU chip(s); JAX has "
              f"{len(jax.devices())} {backend} device(s). No result.",
              file=sys.stderr)
        return False
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="also keep the raw trace of a --trace 1 run here")
    args = ap.parse_args(argv)

    cell = load_cell(args.workload)
    if not prepare(cell.chips):
        return 2
    line = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                    trace_dir=args.trace_dir)
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
