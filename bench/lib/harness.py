"""The benchmark harness: one cell, one seed, one measured window.

Everything that belongs to one configuration, traffic mix or per-layer
metric is found by its name in ``BENCHMARK.json``:

- a configuration is the JSON file that its entry's ``file`` names;
- a traffic mix ``<mix>`` is ``bench/mixes/<mix>.json``; its ``driver``
  key names the driver ``bench/drivers/<driver>.py`` that runs it;
- a per-layer metric ``<name>`` is ``bench/metrics/<name>.py``, whose
  ``read(ctx)`` returns the number or None when it finds nothing to read.

A driver is a module with three functions:

``setup(ctx) -> state``
    make the inputs from the seed, build the program's objects and warm
    every shape the window uses; ``state["graph"]`` holds what the
    program keeps on the device.
``window(ctx, state, seconds) -> Window``
    drive the program for ``seconds`` and return what was attempted,
    what failed, the raw samples to check and the facts metrics read.
``finish(ctx, state, win) -> (checks, end_to_end)``
    after the program's state is freed: compare the samples with the
    plain reference (``lib/reference.py``) and compute the end-to-end
    numbers the driver owns.
``control(ctx, ref, rows, n, seconds) -> Window``
    the window as the correctness control fills it: the reference one
    step below the stated precision, in the program's place
    (``bench/control.py``; never called by a run).
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional

from bench.lib import xtrace
from bench.lib.peaks import peaks

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"


class NoChip(RuntimeError):
    """JAX sees no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Check:
    """One compared number beside its limit (``value <= limit`` passes)."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Window:
    attempted: int
    failed: int
    window_s: float
    samples: Any = None
    facts: Dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Ctx:
    cell: dict
    config: dict
    mix: dict
    seed: int
    chips: int
    log: Callable[[str], None]


@dataclasses.dataclass
class MetricCtx:
    """What a per-layer metric's reader may read."""

    window_s: float
    facts: Dict[str, Any]
    trace: Any                   # lib.xtrace.Trace
    peaks: dict


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# -- discovery ----------------------------------------------------------------

def load_spec(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def find(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json "
                   f"(known: {[e['name'] for e in entries]})")


def load_config(spec: dict, name: str, root: Path = ROOT) -> dict:
    with open(root / find(spec["configs"], name, "config")["file"]) as f:
        return json.load(f)


def load_mix(name: str, root: Path = ROOT) -> dict:
    with open(root / "bench" / "mixes" / f"{name}.json") as f:
        return json.load(f)


def _load_module(path: Path) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(
        "bench_plugin_" + path.stem.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_driver(name: str, root: Path = ROOT) -> ModuleType:
    return _load_module(root / "bench" / "drivers" / f"{name}.py")


def load_metric(name: str, root: Path = ROOT) -> ModuleType:
    return _load_module(root / "bench" / "metrics" / f"{name}.py")


def metrics_for(spec: dict, cell: str, trace: bool) -> List[dict]:
    """The cell's end-to-end metrics (``trace`` false) or per-layer ones."""
    entries = spec["per_layer" if trace else "end_to_end"]
    return [m for m in entries if cell in m.get("workloads", [cell])]


# -- the device ---------------------------------------------------------------

def require_chip(chips: int):
    """The first ``chips`` TPU devices, or :class:`NoChip`."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r}); "
                     f"the benchmark measures only on the chip")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX sees "
                     f"{len(devs)}")
    return devs[:chips]


def enable_compile_cache() -> str:
    """The program's persistent compile cache (fixed ``.jax_cache/`` in the
    checkout unless ``JAX_COMPILATION_CACHE_DIR`` says otherwise), holding
    every program however fast it compiled, so that later runs load it."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache as enable
    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Programs compiled, and programs loaded from the persistent cache,
    since ``reset``. JAX reports a cache load as a compile event too."""

    def __init__(self):
        self._events = 0
        self.cache_hits = 0

    def __enter__(self) -> "CompileCounter":
        import jax.monitoring as mon
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        import jax.monitoring as mon
        mon.unregister_event_duration_listener(self._on_duration)
        mon.unregister_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self._events += 1

    def _on_event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @property
    def compiles(self) -> int:
        return self._events - self.cache_hits

    def reset(self) -> None:
        self._events = self.cache_hits = 0


# -- one run ------------------------------------------------------------------

def _memory_peak(devices) -> Optional[int]:
    """The device memory's peak on the fullest chip: the arrays' peak
    (``peak_bytes_in_use``) plus the peak of the runtime's reservation for
    program temporaries (``peak_bytes_reserved``), where padded copies and
    gathered operands live."""
    got = []
    for d in devices:
        st = d.memory_stats() or {}
        if "peak_bytes_in_use" in st:
            got.append(st["peak_bytes_in_use"]
                       + st.get("peak_bytes_reserved", 0))
    return max(got) if got else None


def _window(driver, ctx, state, seconds: float, trace: bool,
            chips: int):
    """The measured window, under the profiler when ``trace``."""
    import jax
    if not trace:
        with jax.profiler.TraceAnnotation(xtrace.WINDOW):
            return driver.window(ctx, state, seconds), None
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(xtrace.WINDOW):
                win = driver.window(ctx, state, seconds)
        finally:
            jax.profiler.stop_trace()
        tr = xtrace.load(trace_dir)
        tr.n_devices = chips
        return win, tr
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool,
             t_start: float, *, spec: Optional[dict] = None,
             config: Optional[dict] = None, mix: Optional[dict] = None,
             chip: bool = True) -> dict:
    """Run one cell and return the result line's object.

    ``chip=False`` (tests only) skips the look for a TPU and names the
    device JAX has; ``config`` / ``mix`` replace the cell's files.
    """
    import jax
    spec = spec if spec is not None else load_spec()
    cell = find(spec["workloads"], cell_name, "workload")
    chips = int(cell["chips"])
    devices = require_chip(chips) if chip else jax.devices()[:chips]
    cache_dir = enable_compile_cache() if chip else "off"
    config = config if config is not None else load_config(spec,
                                                           cell["config"])
    mix = mix if mix is not None else load_mix(cell["traffic"])
    driver = load_driver(mix["driver"])
    ctx = Ctx(cell=cell, config=config, mix=mix, seed=int(seed),
              chips=chips, log=log)
    dev0 = devices[0]
    log(f"[bench] {cell_name} seed={seed} on {dev0.platform} "
        f"{dev0.device_kind} x{len(devices)}; compile cache {cache_dir}")

    with CompileCounter() as counter:
        state = driver.setup(ctx)
        setup_s = time.monotonic() - t_start
        log(f"[bench] setup_s={setup_s:.3f} (compiled {counter.compiles}, "
            f"loaded from the cache {counter.cache_hits})")
        counter.reset()
        win, tr = _window(driver, ctx, state, seconds, trace, chips)
        log(f"[bench] window {win.window_s:.3f} s: attempted "
            f"{win.attempted}, failed {win.failed}; compiled in the "
            f"window {counter.compiles}, loaded from the cache "
            f"{counter.cache_hits}")
    mem_peak = _memory_peak(devices)
    log(f"[bench] memory after the window: {dev0.memory_stats()}")

    state.pop("graph", None)             # free the program's device state
    gc.collect()
    t_ref = time.monotonic()
    checks, e2e = driver.finish(ctx, state, win)
    log(f"[bench] reference and checks {time.monotonic() - t_ref:.3f} s")
    e2e["setup_s"] = (setup_s, "s")

    metrics: Dict[str, dict] = {}
    if not trace:
        for m in metrics_for(spec, cell_name, False):
            if m["name"] not in e2e:
                raise KeyError(f"driver {mix['driver']!r} reports no "
                               f"{m['name']!r}")
            metrics[m["name"]] = {"value": e2e[m["name"]][0],
                                  "unit": m["unit"]}
    else:
        mctx = MetricCtx(window_s=win.window_s, facts=win.facts, trace=tr,
                         peaks=peaks(dev0.device_kind) if chip else {})
        for m in metrics_for(spec, cell_name, True):
            v = load_metric(m["name"]).read(mctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}
    out = {"correct": bool(checks) and all(c.ok for c in checks),
           "attempted": win.attempted, "failed": win.failed,
           "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        out["breakdown"] = {"device_ops": [list(x) for x in tr.top_ops(10)],
                            "idle_gaps": [list(x) for x in tr.idle_gaps(10)]}
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    return out


def print_result(out: dict) -> None:
    """Checks as the last lines of stderr; the result as stdout's last."""
    for name, c in out["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        log(f"check {name} = {c['value']!r} (limit {c['limit']!r}) "
            f"{verdict}")
    print(json.dumps(out), flush=True)
