"""CPU tests of the chip benchmark: the trace reduction, the operation and
byte counts, the cells' files, the refusal without a TPU, a cell defined
by data alone run end to end, the control, and the faults that have to
turn ``correct`` false.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

Nothing here describes a chip topology; the runs use tiny data-only cells
(``bench/tests/data``) with the harness's look for a chip skipped.
"""
from __future__ import annotations

import dataclasses
import io
import json
import math
import os
import shutil
import subprocess
import sys
import time

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
DATA = os.path.join(BENCH, "tests", "data")
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import compare, counts, harness, peaks, trace  # noqa: E402


def _spec(path):
    with open(path) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# trace reduction on a small synthetic trace
# ---------------------------------------------------------------------------


def _synthetic():
    # chip 0: a scan's while op around two overlapping fusions, the AMP
    # kernel, an all-reduce partly hidden behind compute, the forward
    # projection kernel and the adjoint's; chip 1: one op.  Host spans on
    # the same clock.  Names as the trace prints them, shortened.
    ops0 = [(0, 60, "while.218"), (0, 40, "fusion.1"), (30, 60, "fusion.2"),
            (100, 150, "amp_decode_fused.19"), (140, 200, "all-reduce.7"),
            (220, 250, "vmap_jit_ota_project__.19"),
            (250, 255, "vmap_jit_ota_project_t__.4")]
    ops1 = [(0, 100, "fusion.1")]
    spans = [(0, 300, "bench:window"), (0, 120, "bench:round"),
             (120, 300, "bench:serve"), (200, 230, "bench:decode")]
    return trace.Trace(device_ops={0: ops0, 1: ops1}, host_spans=spans)


def test_busy_union_and_idle_share():
    tr = _synthetic()
    lo, hi = tr.window()
    assert (lo, hi) == (0, 300)
    # chip 0 union: [0,60] + [100,200] + [220,255] = 60 + 100 + 35
    assert trace.busy_ns(tr.device_ops[0], lo, hi) == 195
    assert trace.idle_frac(tr.device_ops[0], lo, hi) == pytest.approx(
        1 - 195 / 300)
    # averaged over the chips: (195 + 100) / 2 ns
    assert trace.device_busy_s(tr, lo, hi) == pytest.approx(147.5e-9)
    # clipping to a sub-window
    assert trace.busy_ns(tr.device_ops[0], 50, 120) == 10 + 20


def test_events_by_kernel_name():
    tr = _synthetic()
    amp = harness.metric_reader("amp_decode_roofline").NAMES
    fwd = harness.metric_reader("project_roofline").NAMES
    assert trace.seconds_of(tr.device_ops[0], amp, 0, 300) \
        == pytest.approx(50e-9)
    # the forward projection, not the adjoint
    assert trace.seconds_of(tr.device_ops[0], fwd, 0, 300) \
        == pytest.approx(30e-9)
    assert trace.seconds_of(tr.device_ops[0], ("nothing",), 0, 300) == 0


def test_names_and_control_flow_events():
    assert trace.op_name("%amp_decode_fused.19 = f32[64,1,4096]{2,1,0} "
                         "custom-call(u32[1,2] %b)") == "amp_decode_fused.19"
    tr = _synthetic()
    names = [iv[2] for iv in trace.leaves(tr.device_ops[0])]
    assert "while.218" not in names and "fusion.1" in names
    # a partial overlap is not nesting
    assert trace.leaves([(0, 10, "a"), (5, 15, "b")]) == [(0, 10, "a"),
                                                          (5, 15, "b")]


def test_collective_overlap():
    tr = _synthetic()
    # the all-reduce [140, 200] overlaps the kernel [100, 150] by 10 ns
    assert trace.exposed_collective_ns(tr.device_ops[0], 0, 300) == 50


def test_top_ops_and_idle_gaps_named_by_span():
    tr = _synthetic()
    top = trace.top_ops(tr, 0, 300, n=2)
    # fusion.1: (40 + 100) / 2 chips, the largest; the while op is no leaf
    assert top[0] == ["fusion.1", pytest.approx(70e-9)]
    gaps = trace.idle_gaps(tr, 0, 300, n=3)
    # chip 0's gaps: [60,100] in round, [255,300] in serve, [200,220] in
    # decode (the innermost span that covers the gap's middle)
    assert gaps == [["serve", pytest.approx(45e-9)],
                    ["round", pytest.approx(40e-9)],
                    ["decode", pytest.approx(20e-9)]]


def test_metric_readers_on_a_synthetic_trace():
    tr = _synthetic()
    cell = harness.load_cell("smollm_360m.adsgd.m2")
    win = harness.Window(seconds=300e-9, units=2, iterations=2, failed=0)
    pk = peaks.peaks_for("TPU v5 lite")
    ctx = harness.ReadContext(
        trace=tr, lo=0, hi=300, window=win, cell=cell,
        counts={"amp_flops": 1e3, "amp_bytes": 1e3, "project_flops": 1e3,
                "project_bytes": 8e3, "unit_flops": 1e4}, peaks=pk)
    read = {m: harness.metric_reader(m).read(ctx)
            for m in ("idle_frac.round", "amp_decode_roofline",
                      "project_roofline", "publish_serve_s", "mfu.round")}
    assert read["idle_frac.round"] == pytest.approx(1 - 147.5 / 300)
    # roofline: max(flops / peak, bytes / bw) over kernel seconds per chip
    amp_s = 2 * max(1e3 / pk["bf16_flops"], 1e3 / pk["hbm_bytes_per_s"])
    assert read["amp_decode_roofline"] == pytest.approx(
        100 * amp_s / (50e-9 / 2))
    assert read["project_roofline"] == pytest.approx(
        100 * 2 * 8e3 / pk["hbm_bytes_per_s"] / (30e-9 / 2))
    assert read["publish_serve_s"] == pytest.approx(180e-9)
    assert read["mfu.round"] == pytest.approx(
        100 * 2e4 / (300e-9 * pk["bf16_flops"]))
    # the grid's names read the same computations
    assert harness.metric_reader("idle_frac.grid").read(ctx) \
        == read["idle_frac.round"]
    assert harness.metric_reader("mfu.grid").read(ctx) == read["mfu.round"]
    # a reader that finds nothing returns nothing, never 0
    empty = dataclasses.replace(ctx, trace=trace.Trace({}, []))
    for m in ("idle_frac.round", "amp_decode_roofline", "project_roofline",
              "publish_serve_s"):
        assert harness.metric_reader(m).read(empty) is None


# ---------------------------------------------------------------------------
# operation and byte counts against hand counts
# ---------------------------------------------------------------------------


def test_counts_at_small_shapes():
    # 6 N tokens
    assert counts.model_train_flops(10, 3) == 180
    # 2 blocks of A (4 x 8): 2 * 4 * 8 multiply-adds each
    assert counts.project_flops(2, 4, 8) == 128
    assert counts.project_bytes(2, 4, 8) == 4 * 2 * (8 + 4)
    # (10 + 4 * 3) * 4 * 8 per block, 2 blocks
    assert counts.amp_blocked_flops(2, 4, 8, 3) == 22 * 32 * 2
    assert counts.amp_blocked_bytes(2, 4, 8) == 4 * 2 * (4 + 8)
    assert counts.dense_project_flops(3, 5, 2) == 60
    # two matvecs per iteration and one for the debias
    assert counts.amp_dense_flops(3, 5, 2) == 2 * 15 * 5
    assert counts.softmax_regression_flops(2, 3, 4) == 96
    assert counts.softmax_eval_flops(2, 3, 4) == 96
    pk = {"bf16_flops": 10.0, "hbm_bytes_per_s": 2.0}
    assert counts.roofline_seconds(100, 10, pk) == 10      # compute bound
    assert counts.roofline_seconds(10, 100, pk) == 50      # memory bound


def test_peaks_table_refuses_unknown_devices():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in v5e["source"]
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


def test_compare_numbers_by_hand():
    assert compare.loss_gap([1.0, 2.0], [1.0, 2.2]) == pytest.approx(
        0.2 / 2.2)
    ref = {"a": 1.0, "b": 4.0, "c": 1e-6}
    prog = {"a": 1.1, "b": 4.0, "c": 0.0}
    # against max(own norm, median norm 1.0)
    assert compare.norm_gap(prog, ref) == pytest.approx(0.1)
    assert compare.moved_leaves({"a": 1.0, "b": 2.0, "c": 1e-6}) == ["a", "b"]
    logits = [[0.0, 3.0, 1.0], [2.0, 0.5, 0.0]]
    assert compare.served_gap(logits, [2, 0]) == pytest.approx(2.0)


def test_judge_holds_every_number_to_its_limit():
    ok, out = harness.judge({"x": 0.1, "y": 0.0}, {"x": 0.2, "y": 0})
    assert ok and out["x"] == {"value": 0.1, "limit": 0.2}
    assert not harness.judge({"x": 0.3}, {"x": 0.2})[0]
    assert not harness.judge({"x": math.nan}, {"x": 0.2})[0]
    with pytest.raises(KeyError):
        harness.judge({}, {"x": 0.2})


# ---------------------------------------------------------------------------
# every cell and metric of BENCHMARK.json loads by name from its files
# ---------------------------------------------------------------------------


def test_every_cell_and_metric_loads_by_name():
    spec = _spec(os.path.join(ROOT, "BENCHMARK.json"))
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.end_to_end and cell.per_layer
        assert {"setup_s"} < {m["name"] for m in cell.end_to_end}
        assert callable(harness.loop_module(cell).Loop)
        assert hasattr(cell.reference(), "init_params")
        assert cell.limits
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        assert callable(harness.metric_reader(m["name"]).read)
        for w in m["workloads"]:
            cell = harness.load_cell(w)
            assert m["moves"] in {x["name"] for x in cell.end_to_end}


def test_a_cell_defined_by_data_alone_loads():
    spec = _spec(os.path.join(DATA, "BENCHMARK.json"))
    for name in ("tiny.llm", "tiny.grid"):
        cell = harness.load_cell(name, ROOT, spec=spec, data=DATA)
        assert cell.traffic["loop"] in ("serve_while_train", "sweep_grid")
        assert cell.limits


# ---------------------------------------------------------------------------
# refusals
# ---------------------------------------------------------------------------


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "mnist_mlp.fig4_grid", "--seed", "5000000000", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_command_exits_nonzero_without_a_tpu():
    r = _run_py(ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_command_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run_py(str(tmp_path))
    assert r.returncode != 0
    assert r.stdout.strip() == ""


# ---------------------------------------------------------------------------
# whole runs of the tiny data-only cells, sound and with faults planted
# ---------------------------------------------------------------------------


@pytest.fixture
def cpu_harness(monkeypatch):
    """The harness with its look for a chip skipped, the CPU's peaks taken
    to be a v5e's, and JAX's compilation cache off."""
    import jax

    monkeypatch.setattr(harness, "require_accelerator", lambda chips: None)
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    import repro.launch.cache as cache

    monkeypatch.setattr(cache, "enable_compile_cache",
                        lambda: ("off", {"hits": 0, "misses": 0}))
    yield
    jax.config.update("jax_default_matmul_precision", None)


def run_tiny(name, seed=3000000007, seconds=1.0, trace_on=False):
    """One whole run of a tiny cell; returns the result line."""
    spec = _spec(os.path.join(DATA, "BENCHMARK.json"))
    cell = harness.load_cell(name, ROOT, spec=spec, data=DATA)
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run(name, seed, seconds, trace_on, time.perf_counter(),
                     ROOT, out=out, err=err, cell=cell)
    assert rc == 0
    lines = out.getvalue().strip().splitlines()
    line = json.loads(lines[-1])
    assert list(line)[-1] == "checks"
    tail = err.getvalue().strip().splitlines()
    assert all(t.startswith("check ") for t in tail[-len(line["checks"]):])
    return line


@pytest.mark.parametrize("name", ["tiny.llm", "tiny.grid"])
def test_sound_run_is_correct(cpu_harness, name):
    line = run_tiny(name)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert "setup_s" in line["metrics"]
    assert line["device"]["platform"] == "cpu"


def test_traced_run_reports_the_window(cpu_harness):
    line = run_tiny("tiny.llm", trace_on=True)
    assert line["correct"] is True
    assert line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    assert line["metrics"]["publish_serve_s"]["value"] > 0


def _state_unchanged(cls):
    orig = cls._round

    def stuck(self, sch, *args, **kw):
        carry = args[-4] if cls.__name__ == "CompiledExperiment" else args[0]
        _, out = orig(self, sch, *args, **kw)
        return carry, out
    return stuck


def _half_batch_llm(monkeypatch):
    from repro.train.fedllm import CompiledFedLLM

    orig = CompiledFedLLM._device_batch

    def half(self, key):
        b = orig(self, key)
        return {k: v[: self.batch // 2] for k, v in b.items()}
    monkeypatch.setattr(CompiledFedLLM, "_device_batch", half)


def _half_batch_grid(monkeypatch):
    import repro.experiments.engine as engine

    orig = engine.device_grads

    def half(params, unravel, xd, yd, momenta, **kw):
        b = xd.shape[1] // 2
        return orig(params, unravel, xd[:, :b], yd[:, :b], momenta, **kw)
    monkeypatch.setattr(engine, "device_grads", half)


def _no_exchange(monkeypatch, module, name):
    """The last half of the devices' frames never reach the server; their
    error state is left as it was."""
    import jax.numpy as jnp

    orig = getattr(module, name)

    def lossy(scheme, grads, deltas, *args):
        h = max(1, grads.shape[0] // 2)
        out = orig(scheme, grads[:h], deltas[:h], *args)
        return (out[0], jnp.concatenate([out[1], deltas[h:]]),) + out[2:]
    monkeypatch.setattr(module, name, lossy)


def _token_altered(monkeypatch):
    import jax.numpy as jnp

    import repro.train.serve as serve

    orig = serve.make_serve_step

    def altered(*a, **kw):
        step = orig(*a, **kw)

        def bump(fn):
            def wrapped(*args):
                logits, cache = fn(*args)
                top = jnp.argmax(logits, -1)
                nxt = (top + 1) % logits.shape[-1]
                return logits + 1e4 * (jnp.arange(logits.shape[-1])
                                       == nxt[..., None]), cache
            return wrapped
        return dataclasses.replace(step, prefill_fn=bump(step.prefill_fn),
                                   decode_fn=bump(step.decode_fn))
    monkeypatch.setattr(serve, "make_serve_step", altered)


FAULTS = {
    ("tiny.llm", "state_unchanged"): lambda mp: mp.setattr(
        _llm(), "_round", _state_unchanged(_llm())),
    ("tiny.llm", "half_batch"): _half_batch_llm,
    ("tiny.llm", "no_exchange"): lambda mp: _no_exchange(
        mp, __import__("repro.train.fedllm", fromlist=["x"]),
        "encode_round"),
    ("tiny.llm", "token_altered"): _token_altered,
    ("tiny.grid", "state_unchanged"): lambda mp: mp.setattr(
        _ce(), "_round", _state_unchanged(_ce())),
    ("tiny.grid", "half_batch"): _half_batch_grid,
    ("tiny.grid", "no_exchange"): lambda mp: _no_exchange(
        mp, __import__("repro.experiments.engine", fromlist=["x"]),
        "round_simulated"),
}


def _llm():
    from repro.train.fedllm import CompiledFedLLM
    return CompiledFedLLM


def _ce():
    from repro.experiments.engine import CompiledExperiment
    return CompiledExperiment


@pytest.mark.parametrize("cell,fault", sorted(FAULTS))
def test_a_fault_in_the_timed_path_is_not_correct(cpu_harness, monkeypatch,
                                                  cell, fault):
    FAULTS[(cell, fault)](monkeypatch)
    line = run_tiny(cell)
    assert line["correct"] is False, line["checks"]


@pytest.mark.parametrize("name", ["tiny.llm", "tiny.grid"])
def test_the_control_is_not_correct(cpu_harness, name):
    spec = _spec(os.path.join(DATA, "BENCHMARK.json"))
    cell = harness.load_cell(name, ROOT, spec=spec, data=DATA)
    loop = harness.loop_module(cell).Loop(cell, 3000000009, harness.span)
    readings = loop.control_readings(faults=False)["control"]
    ok, compared = harness.judge(readings, cell.limits)
    assert not ok, compared


def test_control_script_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "bench/control.py", "--workload",
                        "mnist_mlp.fig4_grid", "--seeds", "1"], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0 and r.stdout.strip() == ""
