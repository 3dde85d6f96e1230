"""The benchmark's harness: finds a cell's files by name, runs set-up, the
measured window and the correctness check, and prints the result line.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``.  Its pieces are
found by name, so a new cell, configuration, traffic mix or per-layer
metric is new files and entries, never an edit:

* the configuration as run (its ``file`` in ``BENCHMARK.json``, e.g.
  ``bench/configs/<config>.json``), with its plain reference beside it;
* ``bench/traffic/<traffic>.json``: the traffic's parameters; its
  ``loop`` names ``bench/loops/<loop>.py``, the general loop of that
  kind of traffic;
* ``bench/metrics/<metric>.py``: one reader per per-layer metric;
* ``bench/limits/<workload>.json``: the limit of each number compared.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    limits: Dict[str, float]
    config_dir: str

    def reference(self):
        """The configuration's plain reference, the module its file names
        (a path relative to the configuration's file)."""
        return load_module(os.path.join(self.config_dir,
                                        self.config["reference"]))


def load_module(path: str):
    """Import a file by path (metric files have dots in their names)."""
    name = "bench_" + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise ImportError(f"cannot import {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def _for_cell(entry: Dict[str, Any], cell: str) -> bool:
    return cell in entry.get("workloads", [cell])


def load_cell(name: str, root: str = ROOT, spec: Optional[Dict] = None,
              data: Optional[str] = None) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` (or of ``spec``) with
    all its files.  Traffic and limits are read under ``data`` (default
    ``<root>/bench``); a configuration's ``file`` is relative to ``root``."""
    if spec is None:
        spec = _read_json(os.path.join(root, "BENCHMARK.json"))
    data = data or os.path.join(root, "bench")
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r}; known: {sorted(work)}")
    w = work[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config_file = os.path.join(root, configs[w["config"]]["file"])
    config = _read_json(config_file)
    traffic = _read_json(os.path.join(data, "traffic",
                                      w["traffic"] + ".json"))
    e2e = [m for m in spec["end_to_end"] if _for_cell(m, name)]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in e2e_names)]
    limits = _read_json(os.path.join(data, "limits", name + ".json"))
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e,
                per_layer=layer, limits=limits,
                config_dir=os.path.dirname(config_file))


def loop_module(cell: Cell):
    return load_module(os.path.join(BENCH, "loops",
                                    cell.traffic["loop"] + ".py"))


def metric_reader(name: str):
    return load_module(os.path.join(BENCH, "metrics", name + ".py"))


def require_accelerator(chips: int):
    """Raise unless JAX sees a TPU with at least ``chips`` devices."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"JAX found no TPU (platform "
                            f"{devs[0].platform!r})")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found "
                            f"{len(devs)}")


@contextlib.contextmanager
def span(name: str):
    """A host span ``bench:<name>`` in the profiler's trace."""
    import jax

    with jax.profiler.TraceAnnotation("bench:" + name):
        yield


def device_info(chips: int) -> Dict[str, Any]:
    import jax

    devs = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs[:chips]]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(max(peaks))}


@dataclasses.dataclass
class Window:
    seconds: float
    units: int
    iterations: int
    failed: int


def run_window(loop, seconds: float) -> Window:
    """Iterate until the next iteration would end past ``seconds``; the
    window ends on an iteration boundary, after all its work is done."""
    t0 = time.perf_counter()
    units = iters = failed = 0
    with span("window"):
        while True:
            ti = time.perf_counter()
            done, bad = loop.iteration()
            units, failed, iters = units + done, failed + bad, iters + 1
            last = time.perf_counter() - ti
            if time.perf_counter() - t0 + last > seconds:
                break
        failed += loop.finish()
    return Window(time.perf_counter() - t0, units, iters, failed)


def traced_window(loop, seconds: float, cell: Cell, peaks: Dict):
    """The window under the profiler; returns (Window, per-layer metrics,
    device busy/window seconds, breakdown)."""
    import jax

    from bench import trace as tr

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    log_dir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            win = run_window(loop, seconds)
        finally:
            jax.profiler.stop_trace()
        trace = tr.load(log_dir)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    lo, hi = trace.window()
    ctx = ReadContext(trace=trace, lo=lo, hi=hi, window=win, cell=cell,
                      counts=loop.counts(), peaks=peaks)
    values = {}
    for m in cell.per_layer:
        v = metric_reader(m["name"]).read(ctx)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    dev = {"busy_s": tr.device_busy_s(trace, lo, hi),
           "window_s": (hi - lo) * 1e-9}
    breakdown = {"device_ops": tr.top_ops(trace, lo, hi),
                 "idle_gaps": tr.idle_gaps(trace, lo, hi)}
    return win, values, dev, breakdown, tr.summary(trace)


@dataclasses.dataclass
class ReadContext:
    """What a per-layer metric reader may read."""
    trace: Any
    lo: float
    hi: float
    window: Window
    cell: Cell
    counts: Dict[str, Any]
    peaks: Dict[str, Any]

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9


def judge(checks: Dict[str, float], limits: Dict[str, float]):
    """(correct, {name: {"value", "limit"}}): every number at or under its
    limit, and finite."""
    missing = sorted(set(limits) - set(checks))
    if missing:
        raise KeyError(f"no reading for the limits {missing}")
    out = {k: {"value": float(checks[k]), "limit": float(limits[k])}
           for k in sorted(limits)}
    ok = all(math.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in out.values())
    return ok, out


def run(workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, root: str = ROOT, out=None, err=None,
        cell: Optional[Cell] = None) -> int:
    """One run of one cell; prints the result line last on ``out``.

    ``cell`` (default: ``load_cell(workload, root)``) lets a test drive a
    cell that is defined by data outside ``BENCHMARK.json``."""
    out = out or sys.stdout
    err = err or sys.stderr
    cell = cell or load_cell(workload, root)
    require_accelerator(cell.chips)
    sys.path.insert(0, os.path.join(root, "src"))
    import jax

    from repro.launch.cache import enable_compile_cache

    from bench import peaks as peaks_mod

    cache_dir, cache_counts = enable_compile_cache()
    peaks = peaks_mod.peaks_for(jax.devices()[0].device_kind)
    loop = loop_module(cell).Loop(cell, seed, span)
    before_loop_setup_s = time.perf_counter() - t_start
    with span("setup"):
        loop.setup()
    setup_s = time.perf_counter() - t_start
    misses_at_window = cache_counts["misses"]
    if trace:
        win, values, dev_extra, breakdown, summary = traced_window(
            loop, seconds, cell, peaks)
    else:
        win = run_window(loop, seconds)
    compiled_in_window = cache_counts["misses"] - misses_at_window
    device = device_info(cell.chips)
    if trace:
        device.update(dev_extra)
    else:
        e2e = loop.end_to_end(win)
        e2e["setup_s"] = setup_s
        e2e["peak_hbm_gib"] = device["memory_peak_bytes"] / 2 ** 30
        values = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                  for m in cell.end_to_end}
    loop.release()
    checks = loop.reference_check()
    correct, compared = judge(checks, cell.limits)
    info = {"workload": workload, "seed": seed, "setup_s": setup_s,
            "before_loop_setup_s": before_loop_setup_s,
            "window_s": win.seconds, "iterations": win.iterations,
            "units": win.units, "compiles_in_window": compiled_in_window,
            "compile_cache": {"dir": cache_dir, **cache_counts},
            "program": loop.info()}
    if trace:
        info["trace"] = summary
    print(json.dumps(info), file=err, flush=True)
    line = {"correct": correct, "attempted": win.units,
            "failed": win.failed, "metrics": values, "device": device}
    if trace:
        line["breakdown"] = breakdown
    line["checks"] = compared
    for k, v in compared.items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})", file=err)
    err.flush()
    print(json.dumps(line), file=out, flush=True)
    return 0
