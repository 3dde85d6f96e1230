"""CPU tests of the stage reduction (``bench/stages.py``) on a synthetic
``.xplane.pb`` laid out as a TPU trace is, and of the committed kernel patterns against the names
the program gives its Pallas kernels.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import harness, stages, trace  # noqa: E402


def _xspace(scoped=True):
    """An XSpace as a TPU trace lays it out.  Chip 0: a scan's ``while``
    around an encode, a threshold under ``vmap``, the AMP kernel under
    ``decode``, a chunk write under ``stream``, then the optimizer and an
    unscoped copy (times in ns from the line's start at 1000 ns).  The
    host plane holds the benchmark's window and the program's spans."""
    cls = stages.xspace_classes()
    space = cls["XSpace"]()
    dev = space.planes.add(name="/device:TPU:0")
    names = {1: "tf_op", 2: "program_id", 3: "hlo_category"}
    for k, v in names.items():
        dev.stat_metadata.add(key=k).value.CopyFrom(
            cls["XStatMetadata"](name=v))
    body = "jit(_lambda_)/while/body/stream"
    ops = [
        ("%while.9 = (f32[2]) while(...)", 0, 100, "jit(_lambda_)/while:"),
        ("%sort.14 = f32[8] sort(...)", 0, 10,
         body + "/encode/vmap(threshold)/sort:"),
        ("%ota_project.19 = f32[2,64,1024] custom-call(...)", 10, 20,
         body + "/encode/vmap(jit(ota_project))/ota_project/pallas_call:"),
        ("%amp_decode_fused.19 = f32[64,1,4096] custom-call(...)", 30, 60,
         body + "/decode/jit(amp_decode_fused)/pallas_call:"),
        ("%dynamic_update_slice.2 = f32[4] dynamic-update-slice(...)", 90,
         10, body + "/dynamic_update_slice:"),
        ("%fusion.3 = f32[4] fusion(...)", 110, 20,
         "jit(_lambda_)/optimizer/mul:"),
        ("%copy.1 = f32[4] copy(...)", 130, 10, ""),
    ]
    modules = dev.lines.add(name="XLA Modules", timestamp_ns=1000)
    dev.event_metadata.add(key=100).value.name = "jit__lambda_(77)"
    modules.events.add(metadata_id=100, offset_ps=0, duration_ps=140_000)
    line = dev.lines.add(name="XLA Ops", timestamp_ns=1000)
    for i, (text, start, dur, path) in enumerate(ops, start=1):
        em = dev.event_metadata.add(key=i).value
        em.name = text
        em.stats.add(metadata_id=2, uint64_value=77)
        em.stats.add(metadata_id=3, str_value="loop fusion")
        if scoped and path:
            em.stats.add(metadata_id=1, str_value=path)
        line.events.add(metadata_id=i, offset_ps=start * 1000,
                        duration_ps=dur * 1000)
    host = space.planes.add(name="/host:CPU")
    py = host.lines.add(name="python", timestamp_ns=1000)
    for i, (name, start, dur) in enumerate(
            [("bench:window", 0, 200), ("repro:round", 0, 120),
             ("repro:serve", 120, 60), ("PjitFunction", 0, 5)], start=1):
        host.event_metadata.add(key=i).value.name = name
        py.events.add(metadata_id=i, offset_ps=start * 1000,
                      duration_ps=dur * 1000)
    return space


def _load(tmp_path, scoped=True):
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        _xspace(scoped).SerializeToString())
    return stages.load(str(tmp_path))


def test_load_reads_scope_paths_modules_and_program_spans(tmp_path):
    st = _load(tmp_path)
    assert st.route == "tf_op"
    ops = st.device_ops[0]
    assert [op.name for _, _, op in ops][:2] == ["sort.14", "while.9"]
    assert ops[0][:2] == (1000, 1010)
    assert {op.module for _, _, op in ops} == {"jit__lambda_(77)"}
    assert st.program_spans == [(1000, 1120, "repro:round"),
                                (1120, 1180, "repro:serve")]


def test_stage_split_of_a_window(tmp_path):
    st = _load(tmp_path)
    units = 2
    sec = {s: stages.stage_seconds(st, s, 1000, 1200, units)
           for s in stages.STAGES}
    assert sec["encode"] == pytest.approx(30e-9 / units)
    assert sec["threshold"] == pytest.approx(10e-9 / units)
    assert sec["decode"] == pytest.approx(60e-9 / units)
    assert sec["stream"] == pytest.approx(100e-9 / units)   # while dropped
    assert sec["optimizer"] == pytest.approx(20e-9 / units)
    assert sec["grads"] is None and sec["serve"] is None
    tr_ = trace.Trace(device_ops={0: [(s, e, op.name) for s, e, op
                                      in st.device_ops[0]]},
                      host_spans=[(1000, 1200, "bench:window")])
    out = stages.split(tr_, st, 1000, 1200, units)
    assert out["partition_sum"] == pytest.approx(out["busy"])
    assert out["unattributed_share"] == pytest.approx(10 / 130)
    assert out["top_ops"][0][:3] == ["amp_decode_fused.19",
                                     "jit__lambda_(77)", "decode"]


def test_a_trace_without_scope_paths_reads_nothing(tmp_path):
    st = _load(tmp_path, scoped=False)
    assert st.route is None
    assert all(stages.stage_seconds(st, s, 1000, 1200, 1) is None
               for s in stages.STAGES)
    assert set(stages.partition(st, 1000, 1200, 1)) == {"none"}


@pytest.mark.parametrize("metric,kernel,matched", [
    ("amp_decode_roofline", "amp_decode_fused.19", True),
    ("amp_decode_roofline", "amp_decode_fused.18", True),
    ("amp_decode_roofline", "ota_project.19", False),
    ("project_roofline", "ota_project.19", True),
    ("project_roofline", "vmap_jit_ota_project__.19", True),
    ("project_roofline", "ota_project_t.4", False),
    ("project_roofline", "ef_sparsify.3", False),
    ("amp_decode_roofline", "ef_sparsify.3", False),
])
def test_kernel_patterns_match_the_named_kernels(metric, kernel, matched):
    names = harness.metric_reader(metric).NAMES
    assert bool(trace.named([(0, 1, kernel)], names)) is matched
