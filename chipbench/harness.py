"""What every cell's run shares: the chip check, the compile cache, the
compile meter, the peaks table, host spans, the traced window and the
result line.

Nothing here knows a configuration, a traffic mix or a metric: those are
files of their own under ``chipbench/``, found by the names that
``BENCHMARK.json`` gives.
"""
from __future__ import annotations

import contextlib
import glob
import json
import os
import pathlib
import sys
import time
from typing import Any, Dict, List, Optional

ROOT = pathlib.Path(__file__).resolve().parents[1]     # the checkout
BENCH_DIR = pathlib.Path(__file__).resolve().parent
CACHE_DIR = ROOT / ".jax_cache"
PROCESS_START = time.perf_counter()


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class Fault(RuntimeError):
    """The run produced no result to compare."""


def load_json(path) -> Any:
    return json.loads(pathlib.Path(path).read_text())


def enable_compile_cache() -> str:
    """JAX's persistent cache: ``$JAX_COMPILATION_CACHE_DIR`` where set,
    else ``.jax_cache/`` at the checkout root (a fixed path, because the
    path is part of the key). Every program is cached, however fast it
    compiled, so that a second run compiles nothing."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = env or str(CACHE_DIR)
    if not env:
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def device_info() -> Dict[str, Any]:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_chips(n: int) -> Dict[str, Any]:
    dev = device_info()
    if dev["platform"] != "tpu":
        raise NoChip(f"JAX found no TPU (platform {dev['platform']!r})")
    if dev["count"] < n:
        raise NoChip(f"the cell needs {n} chips, JAX sees {dev['count']}")
    return dev


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip; an unknown device is an error."""
    table = load_json(BENCH_DIR / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"chipbench/peaks.json")
    return table[device_kind]


def memory_peak_bytes(devices) -> Optional[int]:
    """``peak_bytes_in_use`` of the fullest chip."""
    vals = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            vals.append(int(stats["peak_bytes_in_use"]))
    return max(vals) if vals else None


def seed_key(seed: int):
    """A JAX key from any whole number, 64 bits and more included."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def small_seed(seed: int) -> int:
    """The seed the program's own samplers take (they build 32-bit keys
    from it and add epoch numbers to it)."""
    return seed % (2 ** 31 - 2 ** 20)


class CompileMeter:
    """XLA backend compiles (or persistent-cache retrievals) while open:
    seconds, count and cache hits. JAX's listeners are process-wide, so
    one meter is open at a time."""

    _DURATION = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.seconds = 0.0
        self.count = 0
        self.cache_hits = 0

    def __enter__(self) -> "CompileMeter":
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == self._DURATION:
            self.seconds += secs
            self.count += 1

    def _event(self, event: str, **_kw) -> None:
        if event == self._HIT:
            self.cache_hits += 1

    def snapshot(self) -> Dict[str, float]:
        return {"compile_s": self.seconds, "compiles": self.count,
                "cache_hits": self.cache_hits}


class Spans:
    """Host spans the harness puts around its calls into the program.

    Each span is timed on the host clock and, in a traced run, also
    written into the profiler's trace as a ``TraceAnnotation`` so that
    idle gaps on the device can be put down to what the host was doing.
    """

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.total: Dict[str, float] = {}
        self.count: Dict[str, int] = {}

    @contextlib.contextmanager
    def span(self, name: str):
        ann = None
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(f"bench.{name}")
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            if ann is not None:
                ann.__exit__(None, None, None)
            self.total[name] = self.total.get(name, 0.0) + (t1 - t0)
            self.count[name] = self.count.get(name, 0) + 1

    def reset(self) -> None:
        self.total.clear()
        self.count.clear()


class Tracer:
    """The profiler of a ``--trace 1`` run, host annotations included.

    The trace goes to ``chipbench/.traces/<workload>`` inside the
    checkout and is reduced (``trace_reduce``) once it has stopped.
    """

    def __init__(self, enabled: bool, workload: str):
        self.enabled = enabled
        self.dir = BENCH_DIR / ".traces" / workload
        self.window_s = None
        self._t0 = None

    def start(self) -> None:
        if not self.enabled:
            return
        import shutil
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self._t0 = time.perf_counter()

    def stop(self) -> None:
        if not self.enabled or self._t0 is None:
            return
        import jax
        self.window_s = time.perf_counter() - self._t0
        jax.profiler.stop_trace()
        self._t0 = None

    def reduce(self, num_devices: int):
        if not self.enabled:
            return None
        from chipbench import trace_reduce
        files = glob.glob(str(self.dir / "**" / "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise Fault("the profiler wrote no trace")
        return trace_reduce.reduce_xplane(files[0], num_devices)


def metric(value: float, unit: str) -> Dict[str, Any]:
    return {"value": value, "unit": unit}


def emit(result: Dict[str, Any], checks: List[Dict[str, Any]]) -> None:
    """The numbers compared, each beside its limit, as the last lines on
    standard error; then the result line, last on standard output."""
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def check(name: str, value: float, limit: float) -> Dict[str, Any]:
    """One number compared with its limit: it passes at or below it."""
    return {"name": name, "value": value, "limit": limit,
            "ok": value == value and value <= limit}


def load_module(path: pathlib.Path, name: str):
    """A module from a file whose name may hold dots (``mfu.train.py``)."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_per_layer(names: List[str], record: Dict[str, Any],
                   metrics_dir=None) -> Dict[str, Any]:
    """Each per-layer metric from its reader ``metrics/<name>.py``; a
    reader that finds nothing to read returns None and the metric is
    left out."""
    out = {}
    for name in names:
        mod = load_module(pathlib.Path(metrics_dir or BENCH_DIR / "metrics")
                          / f"{name}.py",
                          f"chipbench_metric_{name.replace('.', '_')}")
        val = mod.read(record)
        if val is not None:
            out[name] = metric(val, mod.UNIT)
    return out
