"""Device seconds per stage of the round, read from a profiler trace.

The program names the stages of a round with ``jax.named_scope``
(``repro.tracing``), and every HLO operation keeps the scope path it was
traced under as its ``op_name`` metadata:
``jit(_lambda_)/while/body/stream/encode/vmap(threshold)/sort``.  On a TPU
the profiler copies that path onto each device operation's event metadata,
as the stat ``tf_op`` (``<op_name>:<op_type>``; JAX leaves the type empty).
``jax.profiler.ProfileData`` does not show metadata stats, so this module
reads the ``.xplane.pb`` itself: the XSpace protocol buffer, through the
few fields it needs (``XSPACE_FIELDS``).  It keeps each device operation's
instruction name, program (module name and program id: two programs can
both be ``jit_seg`` and both have a ``fusion.3``) and scope path, and the program's own ``repro:`` host spans; it attributes
each leaf operation (``bench.trace.leaves``: a scan's ``while`` is dropped,
the work inside it kept) to the stages on its path.

Times are those ``bench.trace.load`` reads (the line's ``timestamp_ns``
plus the event's offset), so a window read from the benchmark's own spans
clips both alike.  ``bench.trace`` is left as it is.

Run as a script it drives one traced window of a cell and prints the
split of the window's device time by stage:

    python3 bench/stages.py --workload <cell> --seed <n> --seconds <s>

Where the programs come from the persistent compile cache, their op names
are those of the build that compiled them, unless the cache key holds the
metadata (``repro.launch.cache.enable_compile_cache`` sets that).
"""
from __future__ import annotations

import dataclasses
import functools
import glob
import json
import os
import re
import shutil
import sys
import tempfile
import time
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

if __package__ in (None, ""):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import trace as tr  # noqa: E402

#: the program's stages (``repro.tracing.STAGES``; a test pins the two
#: equal, so the benchmark imports nothing of the program)
STAGES = ("grads", "stream", "encode", "threshold", "decode", "optimizer",
          "eval", "serve")
#: prefix of the program's own host spans
PROGRAM_SPAN_PREFIX = "repro:"
#: the event-metadata stat that holds an operation's scope path
SCOPE_STAT = "tf_op"
#: transformations JAX writes around a scope on the path (``vmap(encode)``,
#: ``transpose(jvp(grads))``); ``jit(...)`` names a function, not a scope
TRANSFORMS = ("vmap", "jvp", "transpose", "linearize", "vjp", "remat",
              "checkpoint", "pmap")
_WRAPPED = re.compile(r"^(%s)\((.*)\)$" % "|".join(TRANSFORMS))
_PROGRAM = re.compile(r"\((\d+)\)$")

#: the fields of ``tsl/profiler/protobuf/xplane.proto`` read here, by
#: message: (name, number, type, repeated, message type).  Maps are read as
#: their wire form, repeated (key, value) entries.
XSPACE_FIELDS = {
    "XSpace": [("planes", 1, "message", True, "XPlane")],
    "XPlane": [("name", 2, "string", False, None),
               ("lines", 3, "message", True, "XLine"),
               ("event_metadata", 4, "message", True, "EventMetadataEntry"),
               ("stat_metadata", 5, "message", True, "StatMetadataEntry")],
    "EventMetadataEntry": [("key", 1, "int64", False, None),
                           ("value", 2, "message", False, "XEventMetadata")],
    "StatMetadataEntry": [("key", 1, "int64", False, None),
                          ("value", 2, "message", False, "XStatMetadata")],
    "XLine": [("name", 2, "string", False, None),
              ("timestamp_ns", 3, "int64", False, None),
              ("events", 4, "message", True, "XEvent")],
    "XEvent": [("metadata_id", 1, "int64", False, None),
               ("offset_ps", 2, "int64", False, None),
               ("duration_ps", 3, "int64", False, None)],
    "XEventMetadata": [("name", 2, "string", False, None),
                       ("stats", 5, "message", True, "XStat")],
    "XStatMetadata": [("name", 2, "string", False, None)],
    "XStat": [("metadata_id", 1, "int64", False, None),
              ("uint64_value", 3, "uint64", False, None),
              ("int64_value", 4, "int64", False, None),
              ("str_value", 5, "string", False, None),
              ("ref_value", 7, "uint64", False, None)],
}


class Op(NamedTuple):
    """One device operation: instruction, program, scope path."""
    name: str
    module: str
    path: str


ScopedInterval = Tuple[float, float, Op]


@dataclasses.dataclass
class ScopedTrace:
    """Device operations per chip with their scope paths, and the
    program's host spans."""
    device_ops: Dict[int, List[ScopedInterval]]
    program_spans: List[tr.Interval]
    #: the stat the scope paths came from; None when no operation had one
    route: Optional[str] = None


@functools.lru_cache(maxsize=None)
def xspace_classes():
    """Message classes for the XSpace fields in ``XSPACE_FIELDS``."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    field = descriptor_pb2.FieldDescriptorProto
    types = {"int64": field.TYPE_INT64, "uint64": field.TYPE_UINT64,
             "string": field.TYPE_STRING, "message": field.TYPE_MESSAGE}
    fdp = descriptor_pb2.FileDescriptorProto(
        name="bench_xspace.proto", package="bench_xspace", syntax="proto3")
    for msg, fields in XSPACE_FIELDS.items():
        m = fdp.message_type.add(name=msg)
        for name, number, typ, repeated, sub in fields:
            f = m.field.add(name=name, number=number, type=types[typ],
                            label=(field.LABEL_REPEATED if repeated
                                   else field.LABEL_OPTIONAL))
            if sub:
                f.type_name = ".bench_xspace." + sub
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return {msg: message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xspace." + msg))
        for msg in XSPACE_FIELDS}


def _stat_value(stat, stat_names: Dict[int, str]):
    if stat.ref_value:
        return stat_names.get(stat.ref_value, "")
    return stat.str_value or stat.uint64_value or stat.int64_value


def _device_ops(plane, meta, chip_ops: List[ScopedInterval]) -> bool:
    """Append a device plane's ``XLA Ops`` events to ``chip_ops``; True
    when any operation carries a scope path."""
    stat_names = {s.key: s.value.name for s in plane.stat_metadata}
    modules = {}      # program id -> "<module name>(<program id>)"
    for line in plane.lines:
        if line.name == "XLA Modules":
            for e in line.events:
                name = meta[e.metadata_id].name
                m = _PROGRAM.search(name)
                if m:
                    modules[int(m.group(1))] = name
    ops_of: Dict[int, Op] = {}
    scoped = False
    for mid, em in meta.items():
        st = {stat_names.get(s.metadata_id): _stat_value(s, stat_names)
              for s in em.stats}
        path = str(st.get(SCOPE_STAT) or "")
        scoped = scoped or bool(path)
        program = st.get("program_id")
        ops_of[mid] = Op(tr.op_name(em.name),
                         modules.get(program, str(program or "")), path)
    for line in plane.lines:
        if line.name != tr._OPS_LINE:
            continue
        t0 = line.timestamp_ns
        for e in line.events:
            s = t0 + e.offset_ps / 1000
            chip_ops.append((s, s + e.duration_ps / 1000,
                             ops_of[e.metadata_id]))
    return scoped


def from_xspace(space) -> ScopedTrace:
    """The scoped trace of a parsed XSpace."""
    device_ops: Dict[int, List[ScopedInterval]] = {}
    spans: List[tr.Interval] = []
    route = None
    for plane in space.planes:
        meta = {e.key: e.value for e in plane.event_metadata}
        chip = tr._DEVICE_PLANE.match(plane.name)
        if chip:
            ops = device_ops.setdefault(int(chip.group(1)), [])
            if _device_ops(plane, meta, ops):
                route = SCOPE_STAT
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    name = meta[e.metadata_id].name
                    if name.startswith(PROGRAM_SPAN_PREFIX):
                        s = line.timestamp_ns + e.offset_ps / 1000
                        spans.append((s, s + e.duration_ps / 1000, name))
    return ScopedTrace({k: sorted(v) for k, v in device_ops.items()},
                       sorted(spans), route)


def xplane_path(log_dir: str) -> str:
    """The one ``.xplane.pb`` the profiler wrote under ``log_dir``."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {log_dir}, "
                         f"found {len(paths)}")
    return paths[0]


def load(log_dir: str) -> ScopedTrace:
    """Read the one ``.xplane.pb`` the profiler wrote under ``log_dir``."""
    space = xspace_classes()["XSpace"]()
    with open(xplane_path(log_dir), "rb") as f:
        space.ParseFromString(f.read())
    return from_xspace(space)


@functools.lru_cache(maxsize=None)
def stages_on(path: str) -> Tuple[str, ...]:
    """The stages on a scope path, outermost first: its components with
    transformation wrappers taken off (``transpose(jvp(grads))`` is
    ``grads``) and the ``:<op_type>`` suffix of the last one dropped."""
    out = []
    for part in re.sub(r":[^/]*$", "", path).split("/"):
        m = _WRAPPED.match(part)
        while m:
            part = m.group(2)
            m = _WRAPPED.match(part)
        if part in STAGES:
            out.append(part)
    return tuple(out)


def innermost(path: str) -> Optional[str]:
    """The innermost stage on a scope path, or None."""
    on = stages_on(path)
    return on[-1] if on else None


def _leaves(st: ScopedTrace, lo: float, hi: float
            ) -> Dict[int, List[ScopedInterval]]:
    return {chip: tr.leaves(tr.clip(ops, lo, hi))
            for chip, ops in st.device_ops.items()}


def _per_stage(leaves: Dict[int, List[ScopedInterval]]) -> Dict[str, float]:
    """Summed ns of the leaves whose path holds each stage, over chips."""
    tot: Dict[str, float] = {}
    for ivs in leaves.values():
        for s, e, op in ivs:
            for stage in stages_on(op.path):
                tot[stage] = tot.get(stage, 0.0) + (e - s)
    return tot


def stage_seconds(st: ScopedTrace, stage: str, lo: float, hi: float,
                  units: float) -> Optional[float]:
    """Device seconds per unit of work of the leaf operations in [lo, hi]
    whose scope path holds ``stage``, averaged over the chips.  None when
    no operation there carries the stage."""
    if stage not in STAGES:
        raise ValueError(f"unknown stage {stage!r}; stages are {STAGES}")
    leaves = _leaves(st, lo, hi)
    ns = _per_stage(leaves).get(stage)
    if ns is None or not units:
        return None
    return ns * 1e-9 / len(leaves) / units


def _partition(leaves: Dict[int, List[ScopedInterval]], units: float
               ) -> Dict[str, float]:
    tot: Dict[str, float] = {}
    for ivs in leaves.values():
        for s, e, op in ivs:
            key = innermost(op.path) or "none"
            tot[key] = tot.get(key, 0.0) + (e - s)
    n = max(len(leaves), 1) * (units or 1)
    return {k: v * 1e-9 / n for k, v in sorted(tot.items())}


def partition(st: ScopedTrace, lo: float, hi: float, units: float
              ) -> Dict[str, float]:
    """Device seconds per unit of work of the leaf operations in [lo, hi],
    each counted once under the innermost stage on its path (``none``
    when it carries none), averaged over the chips."""
    return _partition(_leaves(st, lo, hi), units)


def _top_ops(leaves: Dict[int, List[ScopedInterval]], units: float,
             n: int = 12) -> List[List]:
    tot: Dict[Tuple[str, str, str], float] = {}
    for ivs in leaves.values():
        for s, e, op in ivs:
            key = (op.name, op.module, innermost(op.path) or "none")
            tot[key] = tot.get(key, 0.0) + (e - s)
    div = max(len(leaves), 1) * (units or 1)
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[*key, ns * 1e-9 / div] for key, ns in ranked]


def split(trace: tr.Trace, st: ScopedTrace, lo: float, hi: float,
          units: float) -> Dict:
    """Every stage's seconds per unit (None where no operation carries
    it), the partition by innermost stage, the device's busy seconds per
    unit, the share of the busy time the partition leaves without a stage,
    and the leaf operations with the most time (name, module, innermost
    stage, seconds per unit)."""
    leaves = _leaves(st, lo, hi)
    div = max(len(leaves), 1) * units
    per = _per_stage(leaves)
    busy = tr.device_busy_s(trace, lo, hi) / units
    part = _partition(leaves, units)
    return {"per_stage": {s: per[s] * 1e-9 / div if s in per else None
                          for s in STAGES},
            "partition": part,
            "partition_sum": sum(part.values()),
            "busy": busy,
            "unattributed_share": part.get("none", 0.0) / busy if busy
            else None,
            "top_ops": _top_ops(leaves, units)}


def run(workload: str, seed: int, seconds: float, root: str) -> Dict:
    """One traced window of a cell, set up as ``bench/run.py`` sets it up;
    returns the stage split, the cell's per-layer metrics and its
    end-to-end metrics from the traced window, and the trace's size and
    reduction times.  No correctness check is made."""
    from bench import harness

    cell = harness.load_cell(workload, root)
    harness.require_accelerator(cell.chips)
    sys.path.insert(0, os.path.join(root, "src"))
    import jax

    from repro.launch.cache import enable_compile_cache

    from bench import peaks as peaks_mod

    enable_compile_cache()
    peaks = peaks_mod.peaks_for(jax.devices()[0].device_kind)
    loop = harness.loop_module(cell).Loop(cell, seed, harness.span)
    loop.setup()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    log_dir = tempfile.mkdtemp(prefix="bench_stages_")
    try:
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            win = harness.run_window(loop, seconds)
        finally:
            jax.profiler.stop_trace()
        size = os.path.getsize(xplane_path(log_dir))
        t0 = time.perf_counter()
        trace = tr.load(log_dir)
        load_s = time.perf_counter() - t0
        scoped = load(log_dir)
        scoped_load_s = time.perf_counter() - t0 - load_s
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    lo, hi = trace.window()
    ctx = harness.ReadContext(trace=trace, lo=lo, hi=hi, window=win,
                              cell=cell, counts=loop.counts(), peaks=peaks)
    per_layer = {m["name"]: harness.metric_reader(m["name"]).read(ctx)
                 for m in cell.per_layer}
    t1 = time.perf_counter()
    out = split(trace, scoped, lo, hi, win.units)
    split_s = time.perf_counter() - t1
    loop.release()
    return {"workload": workload, "seed": seed, "route": scoped.route,
            "units": win.units, "window_s": (hi - lo) * 1e-9,
            "end_to_end": loop.end_to_end(win), "per_layer": per_layer,
            "stages": out, "xplane_bytes": size,
            "device_events": sum(len(v) for v in trace.device_ops.values()),
            "load_s": load_s, "scoped_load_s": scoped_load_s,
            "split_s": split_s}


def main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    print(json.dumps(run(args.workload, args.seed, args.seconds, root)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
