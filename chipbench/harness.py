"""One run of one cell: generate, set up, warm, measure, check, report.

Everything that belongs to one cell is found by name under the benchmark's
root: the cell in ``BENCHMARK.json``, its configuration file, its traffic
file ``chipbench/traffic/<traffic>.json``, its limits
``chipbench/cells/<workload>.json``, the generator
``chipbench/gen/<generator>.py``, the operation ``chipbench/ops/<op>.py``
and one reader per metric, ``chipbench/metrics/<metric>.py``. Adding a
cell adds files and a ``workloads`` entry and edits nothing.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import os
import pathlib
import sys
import tempfile
import threading
import time
import types
import traceback

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"
# A traced window measures at most this long (and at least TRACE_MIN_OPS
# operations): the end-to-end numbers come from the untraced run.
TRACE_SECONDS = 4.0
TRACE_MIN_OPS = 3


class BenchError(Exception):
    """The run cannot produce a result (no chip, a missing kernel, ...)."""


def load_module(path: pathlib.Path):
    """Import the file at ``path`` as a module of its own."""
    name = "chipbench_file_" + "_".join(path.with_suffix("").parts[-2:])
    name = "".join(ch if ch.isalnum() else "_" for ch in name)
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise BenchError(f"missing benchmark file {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path: pathlib.Path):
    if not path.is_file():
        raise BenchError(f"missing benchmark file {path}")
    return json.loads(path.read_text())


def load_cell(root: pathlib.Path, workload: str) -> dict:
    """The cell's entry and every file it names, resolved by name."""
    bench = read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = read_json(root / cfg_entry["file"])
    traffic = read_json(root / "chipbench" / "traffic" / f"{cell['traffic']}.json")
    return {
        "cell": cell,
        "config": config,
        "traffic": traffic,
        "limits": read_json(root / "chipbench" / "cells" / f"{workload}.json")["limits"],
        "end_to_end": [m for m in bench["end_to_end"]
                       if workload in m.get("workloads", [workload])],
        "per_layer": [m for m in bench["per_layer"]
                      if workload in m.get("workloads", [workload])],
        "gen": root / "chipbench" / "gen" / f"{config['generator']}.py",
        "op": root / "chipbench" / "ops" / f"{traffic['op']}.py",
        "metrics_dir": root / "chipbench" / "metrics",
    }


class Data:
    """The cell's matrix in COO form, values in the configuration's dtype."""

    def __init__(self, rows, cols, vals, shape, dtype):
        self.rows = np.asarray(rows, np.int64)
        self.cols = np.asarray(cols, np.int64)
        self.vals = np.asarray(vals).astype(dtype)
        self.shape = tuple(int(s) for s in shape)
        self._cache = {}

    def describe(self) -> str:
        return f"{self.shape[0]}x{self.shape[1]}, nnz {self.rows.size}"

    def arrays(self) -> tuple:
        return self.rows, self.cols, self.vals

    def cached(self, key, make):
        if key not in self._cache:
            self._cache[key] = make()
        return self._cache[key]

    def csr64(self):
        """The matrix as stored, in float64 (scipy)."""
        import scipy.sparse as sp

        return self.cached("csr64", lambda: sp.csr_matrix(
            (self.vals.astype(np.float64), (self.rows, self.cols)),
            shape=self.shape))

    def counts(self):
        from chipbench import floor

        return self.cached("counts", lambda: floor.matrix_counts(
            self.rows, self.cols, self.shape))


def generate(spec: dict, seed: int):
    """The data the cell's operation receives: the one way from a generator
    to an operation.

    A generator returns either a matrix in COO form, the tuple ``(rows,
    cols, vals, shape)``, which becomes a :class:`Data` in the
    configuration's ``dtype``, or an object of the configuration's own
    (a stack of layers, say), handed to the operation untouched. Such an
    object carries its own sizes, taken from the configuration the
    generator was given, and has what the harness and its tests ask of
    any data: ``describe()``, a short line for the log, and ``arrays()``,
    the numpy arrays it is made of.
    """
    cfg = spec["config"]
    out = load_module(spec["gen"]).generate(cfg, seed)
    if isinstance(out, tuple):
        rows, cols, vals, shape = out
        return Data(rows, cols, vals, shape, np.dtype(cfg["dtype"]))
    return out


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


class CacheEvents:
    """Counts JAX's persistent-cache hits and misses in this process."""

    def __init__(self):
        import jax

        self.hits = self.misses = 0

        def listen(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_listener(listen)


def use_cache() -> CacheEvents:
    """Keep the persistent compilation cache at a fixed path inside the
    checkout, for every program, so that only a cell's first run compiles."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return CacheEvents()


def pick_devices(chips: int, require_tpu: bool):
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise BenchError(f"needs a TPU; JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips; JAX found {len(devices)}")
    return devices[:chips]


def peak_bytes(devices, key: str = "peak_bytes_in_use") -> int | None:
    """The largest ``memory_stats()[key]`` over ``devices``."""
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if stats and key in stats:
            peaks.append(int(stats[key]))
    return max(peaks) if peaks else None


class Heartbeat:
    """A host thread that wakes every ``period`` seconds and keeps its
    longest gap between two wakeups and when it began (``perf_counter``
    seconds). A pause of the whole host shows there as well as in an
    operation's latency; a slow device shows only in the latency."""

    def __init__(self, period: float = 0.01):
        self.longest, self.at = 0.0, 0.0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, args=(period,),
                                        daemon=True)
        self._thread.start()

    def _run(self, period):
        last = time.perf_counter()
        while not self._stop.wait(period):
            now = time.perf_counter()
            if now - last > self.longest:
                self.longest, self.at = now - last, last
            last = now

    def stop(self):
        self._stop.set()
        self._thread.join()


class GcPauses:
    """Seconds the garbage collector ran, from construction to ``stop``."""

    def __init__(self):
        self.n, self.total, self.longest, self._t = 0, 0.0, 0.0, None
        gc.callbacks.append(self._cb)

    def _cb(self, phase, _info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            d = time.perf_counter() - self._t
            self.n, self.total = self.n + 1, self.total + d
            self.longest = max(self.longest, d)

    def stop(self):
        gc.callbacks.remove(self._cb)


class Clock:
    """Host-clock seconds of named set-up stages."""

    def __init__(self):
        self.s = {}

    @contextlib.contextmanager
    def __call__(self, name):
        t0 = time.perf_counter()
        yield
        self.s[name] = self.s.get(name, 0.0) + time.perf_counter() - t0


def measure(spec: dict, seed: int, seconds: float, trace: bool,
            devices, on_tpu: bool) -> dict:
    """Everything a run reads, before the metrics are computed."""
    import jax

    from chipbench import common, peaks, trace as trace_mod

    traffic = spec["traffic"]
    op = load_module(spec["op"])

    t0 = time.perf_counter()
    data = generate(spec, seed)
    pool = op.inputs(data, traffic, np.random.default_rng([seed, 1]))
    log(f"generate: {time.perf_counter() - t0:.3f} s, {data.describe()}")

    clock = Clock()
    t_setup = time.perf_counter()
    st = op.setup(data, traffic, devices, clock)
    pool_dev = [st.put(x) for x in pool]
    memory = [("set-up", peak_bytes(devices), peak_bytes(devices, "bytes_in_use"))]
    with clock("compile"):
        compiled = st.step.lower(*st.args, pool_dev[0]).compile()
        if on_tpu:
            missing = st.kernels - common.tpu_kernel_names(compiled.as_text())
            if missing:
                raise BenchError(f"not compiled as tpu_custom_call: {sorted(missing)}")
        for _ in range(2):
            jax.block_until_ready(compiled(*st.args, pool_dev[0]))
    setup_s = time.perf_counter() - t_setup
    memory.append(("warm-up", peak_bytes(devices), peak_bytes(devices, "bytes_in_use")))

    window = min(seconds, TRACE_SECONDS) if trace else seconds
    min_ops = TRACE_MIN_OPS if trace else 1
    sample_rng = np.random.default_rng([seed, 2])
    k = int(traffic["sample"])
    kept, starts, lat, dispatch = [], [], [], []
    gc_pauses = GcPauses()
    tmp = tempfile.TemporaryDirectory(prefix="chipbench_trace_") if trace else None
    annotate = (jax.profiler.TraceAnnotation if trace
                else lambda _name: contextlib.nullcontext())
    if trace:
        jax.profiler.start_trace(tmp.name)
    heartbeat = Heartbeat()
    t_start = t1 = time.perf_counter()
    i = 0
    while True:
        t0 = time.perf_counter()
        with annotate("dispatch"):
            out = compiled(*st.args, pool_dev[i % len(pool_dev)])
        td = time.perf_counter()
        with annotate("wait"):
            jax.block_until_ready(out)
        t1 = time.perf_counter()
        starts.append(t0 - t_start)
        lat.append(t1 - t0)
        dispatch.append(td - t0)
        # Only the compared array of a sampled answer stays on the device,
        # and nothing else of this operation lives into the next dispatch.
        if len(kept) < k:
            kept.append((i, st.output(out)))
        else:
            j = int(sample_rng.integers(0, i + 1))
            if j < k:
                kept[j] = (i, st.output(out))
        del out
        i += 1
        if t1 - t_start >= window and i >= min_ops:
            break
    window_s = t1 - t_start
    heartbeat.stop()
    gc_pauses.stop()
    q = sorted(lat)
    slow = sorted(range(i), key=lambda j: -lat[j])[:3]
    log(f"window: {i} ops in {window_s:.6f} s; latency s min {q[0]:.6f} "
        f"median {q[len(q) // 2]:.6f} max {q[-1]:.6f}; first "
        + " ".join(f"{x:.6f}" for x in lat[:3])
        + "; slowest (op, start in window, latency, dispatch) "
        + " ".join(f"({j}, {starts[j]:.3f}, {lat[j]:.6f}, {dispatch[j]:.6f})"
                   for j in slow)
        + f"; gc in window: {gc_pauses.n} collections, {gc_pauses.total:.6f} s, "
        f"longest {gc_pauses.longest:.6f} s; host heartbeat: longest gap "
        f"{heartbeat.longest:.6f} s at {heartbeat.at - t_start:.3f} s")
    if trace:
        jax.profiler.stop_trace()
    peak = peak_bytes(devices)
    memory.append(("window", peak, peak_bytes(devices, "bytes_in_use")))
    log("memory (peak, in use) bytes after " + "; ".join(
        f"{stage} {p} {u}" for stage, p, u in memory))

    reduced = None
    if trace:
        reduced = trace_mod.reduce(trace_mod.collect(tmp.name, st.kernels))
        tmp.cleanup()
    outs = [(j, np.asarray(y)) for j, y in kept]
    grid_steps, kernels = st.grid_steps, st.kernels
    del kept, compiled, st, pool_dev
    gc.collect()

    refs = {}
    checks = {}
    failed = 0
    t_ref = time.perf_counter()
    for j, y in outs:
        q = j % len(pool)
        if q not in refs:
            refs[q] = op.reference(data, traffic, pool[q])
        got = op.check(y, refs[q])
        bad = False
        for name, v in got.items():
            checks[name] = max(checks.get(name, v), v)
            bad |= not (v <= spec["limits"][name])
        failed += bad
    log(f"reference: {time.perf_counter() - t_ref:.3f} s for "
        f"{len(outs)} sampled answers of {i}")

    fb, ff = op.floor(data, traffic)
    kind = devices[0].device_kind
    return {
        "n_ops": i, "window_s": window_s, "latencies_s": lat,
        "setup": {"setup_s": setup_s, **{f"{k}_s": v for k, v in clock.s.items()}},
        "grid_steps": grid_steps, "kernels": sorted(kernels),
        "peak_bytes": peak, "trace": reduced, "chips": len(devices),
        "floor_bytes": fb, "floor_flops": ff,
        "least_time_s": (peaks.least_time_s(fb, ff, kind, len(devices))
                         if on_tpu else None),
        "checks": checks, "failed": failed,
        "correct": bool(outs) and failed == 0 and set(checks) == set(spec["limits"]),
    }


def read_metrics(spec: dict, rec: dict, trace: bool) -> dict:
    """Each of the cell's metrics from its own reader; a reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    ns = types.SimpleNamespace(**rec)
    for m in spec["per_layer" if trace else "end_to_end"]:
        value = load_module(spec["metrics_dir"] / f"{m['name']}.py").read(ns)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: pathlib.Path = ROOT, require_tpu: bool = True,
             cache: bool = True) -> dict:
    """One run of ``workload``: the result object the last line prints.

    Tests run tiny cells on the CPU with ``require_tpu=False`` and
    ``cache=False`` (which leaves JAX's cache settings as they are)."""
    import jax

    spec = load_cell(root, workload)
    devices = pick_devices(int(spec["cell"]["chips"]), require_tpu)
    on_tpu = devices[0].platform == "tpu"
    events = use_cache() if cache else CacheEvents()
    rec = measure(spec, seed, seconds, trace, devices, on_tpu)
    log(f"compile cache: dir={jax.config.jax_compilation_cache_dir} "
        f"hits={events.hits} misses={events.misses}")
    log("set-up: " + " ".join(f"{k}={v}" for k, v in rec["setup"].items()))
    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices), "memory_peak_bytes": rec["peak_bytes"]}
    result = {"correct": rec["correct"], "attempted": rec["n_ops"],
              "failed": rec["failed"],
              "metrics": read_metrics(spec, rec, trace), "device": device}
    if trace and rec["trace"] is not None:
        device["busy_s"] = rec["trace"]["busy_s"]
        device["window_s"] = rec["window_s"]
        result["breakdown"] = {"device_ops": rec["trace"]["device_ops"],
                               "idle_gaps": rec["trace"]["idle_gaps"]}
    result["checks"] = {n: {"value": v, "limit": spec["limits"][n]}
                        for n, v in rec["checks"].items()}
    return result


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="Run one cell of the chip benchmark once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except BenchError as e:
        log(f"chipbench: {e}")
        return 1
    except Exception:  # noqa: BLE001 - any failure means no result
        traceback.print_exc()
        return 1
    for name, c in result["checks"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0
