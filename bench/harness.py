"""The benchmark's machinery, driven by ``BENCHMARK.json`` and files found by
name: ``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json``
(whose ``entry`` names ``bench/entries/<entry>.py``),
``bench/metrics/<metric>.py`` and ``bench/limits/<cell>.json``.

One call of :func:`run_cell` runs one cell once: set-up (JAX, the mesh and
its partition, compile or cache load, one warm unit of work), a measured
window, the comparison with the plain reference, and the metrics.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import random
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Callable, Optional

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ----------------------------------------------------------------------
# Files found by name
# ----------------------------------------------------------------------

def load_spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def find(spec: dict, key: str, name: str) -> dict:
    for item in spec[key]:
        if item["name"] == name:
            return item
    raise KeyError(f"no {key} entry named {name!r} in BENCHMARK.json")


def load_module(path: Path):
    mod_name = "bench_" + path.relative_to(BENCH).with_suffix("").as_posix() \
        .replace("/", "_").replace(".", "_").replace("-", "_")
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def config_file(spec: dict, config: str) -> Path:
    return ROOT / find(spec, "configs", config)["file"]


def traffic_file(traffic: str) -> Path:
    return BENCH / "traffic" / f"{traffic}.json"


def entry_file(entry: str) -> Path:
    return BENCH / "entries" / f"{entry}.py"


def metric_file(metric: str) -> Path:
    return BENCH / "metrics" / f"{metric}.py"


def limits_file(cell: str) -> Path:
    return BENCH / "limits" / f"{cell}.json"


def cell_metrics(spec: dict, cell: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    return [m for m in spec[kind]
            if "workloads" not in m or cell in m["workloads"]]


# ----------------------------------------------------------------------
# Devices, compile cache, compile counting
# ----------------------------------------------------------------------

def configure_jax(cache: Optional[str] = None) -> str:
    """Keep JAX's persistent compilation cache at a fixed place inside the
    checkout (``<checkout>/.jax_cache``), so that only a cell's first run
    in a checkout compiles and two checkouts share nothing.  Every program
    is cached, however quickly it compiled."""
    import jax
    cache = cache or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache


def devices_for(chips: int, require_tpu: bool = True) -> list:
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoAccelerator(f"JAX's default device is {devs[0].platform!r}, "
                            f"not a TPU; nothing is measured")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips, JAX sees "
                            f"{len(devs)}")
    return devs[:chips]


class CompileCounter:
    """Counts XLA compilations and persistent-cache hits as JAX reports
    them, so a window can show that nothing compiled inside it."""

    def __init__(self):
        import jax.monitoring as mon
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif name == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        mon.register_event_listener(on_event)
        mon.register_event_duration_secs_listener(on_duration)

    def snapshot(self) -> dict:
        return dict(compiles=self.compiles, cache_hits=self.cache_hits,
                    cache_misses=self.cache_misses)


# JAX's monitoring listeners cannot be removed, so one counter serves every
# run in a process.
_COUNTER: Optional[CompileCounter] = None


def compile_counter() -> CompileCounter:
    global _COUNTER
    if _COUNTER is None:
        _COUNTER = CompileCounter()
    return _COUNTER


def peak_memory(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# ----------------------------------------------------------------------
# The measured window
# ----------------------------------------------------------------------

class Reservoir:
    """A uniform sample of ``k`` items from a stream, drawn from a seed:
    O(1) host work per item, so sampling costs the window nothing."""

    def __init__(self, k: int, seed: int):
        self.k = k
        self.rng = random.Random(seed)
        self.items: list = []
        self.seen = 0

    def offer(self, item) -> None:
        self.seen += 1
        if len(self.items) < self.k:
            self.items.append(item)
        else:
            j = self.rng.randrange(self.seen)
            if j < self.k:
                self.items[j] = item


def annotate(on: bool) -> Callable[[str], Any]:
    """Host phases as profiler annotations in a traced run, else nothing."""
    if not on:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


@dataclasses.dataclass
class Window:
    """What a measured window did, by the host clock."""
    seconds: float            # first dispatch to last completion
    unit_seconds: list        # each unit (segment or dispatch), dispatch
                              # to completion
    work_per_unit: int        # steps per unit
    compiles: int             # compilations inside the window

    @property
    def units(self) -> int:
        return len(self.unit_seconds)

    @property
    def work(self) -> int:
        return self.units * self.work_per_unit


def drive(dispatch: Callable, state, seconds: float, work_per_unit: int,
          keep: Callable, traced: bool):
    """Back-to-back units until ``seconds`` have passed: each unit is
    dispatched, blocked on, and handed to ``keep(before, after)``.
    Returns the final state and the :class:`Window`."""
    import jax
    mark = annotate(traced)
    counter = compile_counter()
    before = counter.compiles + counter.cache_hits
    times = []
    start = time.perf_counter()
    deadline = start + seconds
    end = start
    with mark("bench.window"):
        while end < deadline:
            a = time.perf_counter()
            with mark("bench.dispatch"):
                out = dispatch(state)
            with mark("bench.block"):
                jax.block_until_ready(out)
            end = time.perf_counter()
            with mark("bench.fold"):
                times.append(end - a)
                keep(state, out)
                state = out
    compiles = counter.compiles + counter.cache_hits - before
    return state, Window(seconds=end - start, unit_seconds=times,
                         work_per_unit=work_per_unit, compiles=compiles)


# ----------------------------------------------------------------------
# One run of one cell
# ----------------------------------------------------------------------

@dataclasses.dataclass
class Context:
    """Everything a metric reader may read."""
    cell: dict
    config: dict
    traffic: dict
    chips: int
    device_kind: str
    setup: dict = dataclasses.field(default_factory=dict)   # seconds by phase
    reference_s: float = 0.0          # the reference's share of set-up time,
                                      # which set-up does not count
    window: Optional[Window] = None
    trace: Optional[Any] = None       # trace_reduce.Summary of a traced run


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


def percentile(values, q: float) -> float:
    """The q-th percentile, linear between order statistics."""
    xs = sorted(values)
    if len(xs) == 1:
        return xs[0]
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def run_cell(cell: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, overrides: Optional[dict] = None,
             trace_dir: Optional[str] = None, t_start: Optional[float] = None,
             substitute: Optional[str] = None,
             cache_dir: Optional[str] = None) -> dict:
    """Run one cell once and return its result line as a dict.

    ``cell`` names an entry of ``workloads`` in BENCHMARK.json;
    ``overrides`` replaces keys of the configuration (the tests' small
    meshes); ``substitute`` names a stand-in for the program's output
    (``"control"``: the reference in the next lower precision) that the
    comparison must reject; ``cache_dir`` moves the compilation cache (the
    tests keep theirs out of the checkout).
    """
    t_start = time.perf_counter() if t_start is None else t_start
    spec = load_spec()
    cell_name = cell
    cell = find(spec, "workloads", cell_name)
    config = json.loads(config_file(spec, cell["config"]).read_text())
    config.update(overrides or {})
    traffic = json.loads(traffic_file(cell["traffic"]).read_text())
    limits = json.loads(limits_file(cell_name).read_text())["limits"]
    entry = load_module(entry_file(traffic["entry"]))
    readers = {}
    kind = "per_layer" if trace else "end_to_end"
    for m in cell_metrics(spec, cell_name, kind):
        readers[m["name"]] = (m, load_module(metric_file(m["name"])))

    t0 = time.perf_counter()
    import jax
    jax_import_s = time.perf_counter() - t0
    cache = configure_jax(cache_dir)
    compile_counter()
    devices = devices_for(cell["chips"], require_tpu)
    jax_init_s = time.perf_counter() - t0
    dev = devices[0]
    log(f"[bench] {cell_name}: {cell['chips']} x {dev.platform} "
        f"{dev.device_kind}; jax {jax.__version__}; compile cache {cache}")
    ctx = Context(cell=cell, config=config, traffic=traffic,
                  chips=cell["chips"], device_kind=dev.device_kind)
    ctx.setup["jax_init_s"] = jax_init_s
    ctx.setup["jax_import_s"] = jax_import_s

    prepared = entry.prepare(ctx, devices, seed)
    ctx.setup["total_s"] = time.perf_counter() - t_start - ctx.reference_s
    log(f"[bench] set-up {ctx.setup['total_s']:.3f}s: "
        + ", ".join(f"{k} {v:.3f}" for k, v in ctx.setup.items()
                    if k != "total_s")
        + f"; reference {ctx.reference_s:.3f}s, not counted")
    log(f"[bench] compiles so far {compile_counter().snapshot()}")

    window_seconds = seconds
    if trace:
        window_seconds = min(seconds, float(traffic["trace_seconds"]))
        trace_root = trace_dir or tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(trace_root)
    try:
        ctx.window = entry.measure(prepared, window_seconds, traced=trace)
    finally:
        if trace:
            jax.profiler.stop_trace()
    w = ctx.window
    log(f"[bench] window {w.seconds:.6f}s, {w.units} units, {w.work} "
        f"{traffic['work']}; compilations inside the window: {w.compiles}")
    if trace:
        from bench import trace_reduce
        ctx.trace = trace_reduce.reduce_dir(trace_root, n_chips=ctx.chips)
        if trace_dir is None:
            shutil.rmtree(trace_root, ignore_errors=True)
        log(f"[bench] traced: busy {ctx.trace.busy_s:.6f}s of "
            f"{ctx.trace.window_s:.6f}s per chip; host view "
            f"{w.seconds / w.work * 1e6:.3f} us per {traffic['work_unit']}")

    mem = peak_memory(devices)
    checks = [Check(n, v, limits[n])
              for n, v in entry.compare(prepared, substitute)]
    failed = sum(0 if c.ok else 1 for c in checks)
    correct = failed == 0 and w.compiles == 0

    metrics = read_metrics(readers, ctx)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    result = {"correct": correct, "attempted": w.units,
              "failed": failed + (1 if w.compiles else 0),
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = ctx.trace.busy_s
        device["window_s"] = ctx.trace.window_s
        result["breakdown"] = ctx.trace.breakdown()
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    if w.compiles:
        result["checks"]["window_compiles"] = {"value": w.compiles,
                                               "limit": 0}
    return result


def read_metrics(readers: dict, ctx: Context) -> dict:
    """Each reader's number, from ``readers`` (name -> (entry of
    BENCHMARK.json, reader module)).  A reader that finds nothing leaves
    its metric out of the line; where the metric's ``workloads`` name this
    cell, that is an error."""
    metrics = {}
    for name, (m, reader) in readers.items():
        value = reader.read(ctx)
        if value is None and "workloads" in m:
            raise RuntimeError(f"metric {name} is listed for "
                               f"{ctx.cell['name']} but found nothing to read")
        if value is None:
            log(f"[bench] metric {name} found nothing to read; left out")
            continue
        metrics[name] = {"value": value, "unit": m["unit"]}
    return metrics


def print_checks(result: dict) -> None:
    for name, c in result["checks"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
