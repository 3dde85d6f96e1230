"""Stage scopes, host spans and the compile counter (``repro/tracing.py``).

The programs' lowered text carries every stage they run, as scope
components of the ops' locations; the trace reduction
(``bench/stages.py``) attributes synthetic device operations to the stages
on their paths; the program's ``repro:`` host spans reach the profiler.
"""
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)

from bench import stages as bench_stages
from repro import tracing
from repro.configs import get_config
from repro.configs.base import OTAConfig, TrainConfig
from repro.data.synthetic import federated_split, make_classification
from repro.experiments import CompiledExperiment, Experiment, round_keys
from repro.experiments.engine import run_checkpointed
from repro.launch.mesh import make_local_mesh
from repro.models import model as model_lib
from repro.train.fedllm import CompiledFedLLM, serve_while_train
from repro.train.serve import make_serve_step


def stages_in(text: str) -> set:
    """Every stage on the scope paths of a lowered module's locations."""
    found = set()
    for path in re.findall(r'loc\("([^"]+)"', text):
        found.update(bench_stages.stages_on(path))
    return found


def test_program_and_reduction_share_the_stage_tuple():
    assert tracing.STAGES == bench_stages.STAGES
    assert tracing.SPAN_PREFIX == bench_stages.PROGRAM_SPAN_PREFIX


def test_unknown_stage_is_refused():
    with pytest.raises(ValueError):
        tracing.stage("encoder")


# ---------------------------------------------------------------------------
# the scopes are in the programs
# ---------------------------------------------------------------------------


def _fed():
    arch = get_config("smollm_360m").reduced()
    ota = OTAConfig(scheme="a_dsgd", projection="blocked", s_frac=0.25,
                    k_frac=0.5, block_size=256)
    return CompiledFedLLM(arch, TrainConfig(compute_dtype="float32"), ota,
                          m=2, batch=2, seq_len=8, chunk_size=1 << 14, seed=0)


def test_fedllm_round_lowers_with_every_stage():
    fed = _fed()
    seg = jax.jit(lambda k, c, t: fed.run_segment({}, k, None, c, t))
    text = seg.lower(round_keys(1, 0), fed.carry0(),
                     jnp.int32(0)).as_text(debug_info=True)
    assert stages_in(text) == {"grads", "stream", "encode", "threshold",
                               "decode", "optimizer"}


@pytest.fixture(scope="module")
def mnist_like():
    (xtr, ytr), (xte, yte) = make_classification(
        n_train=400, n_test=100, dim=32, noise=2.0, seed=3)
    xd, yd = federated_split(xtr, ytr, m=4, b=32, iid=True, seed=0)
    return xd, yd, xte, yte


@pytest.mark.parametrize("scheme", ["a_dsgd", "d_dsgd"])
@pytest.mark.parametrize("masked", [False, True], ids=["plain", "masked"])
def test_engine_round_lowers_with_every_stage(mnist_like, scheme, masked):
    xd, yd, xte, yte = mnist_like
    cfg = OTAConfig(scheme=scheme, s_frac=0.5, k_frac=0.25, p_avg=500.0,
                    total_steps=4, projection="dense", amp_iters=3)
    ce = CompiledExperiment(xd, yd, xte, yte,
                            Experiment(cfg=cfg, steps=2, lr=1e-3))
    mask = jnp.ones((4,), jnp.float32) if masked else None
    seg = jax.jit(lambda k, c: ce.run_segment({}, k, mask, c, 0))
    text = seg.lower(round_keys(2, 0),
                     ce._carry0()).as_text(debug_info=True)
    assert stages_in(text) == {"grads", "encode", "threshold", "decode",
                               "optimizer", "eval"}


def test_serve_step_lowers_under_serve():
    arch = get_config("smollm_360m").reduced()
    serve = make_serve_step(arch, make_local_mesh(), 2, 8)
    params = jax.eval_shape(
        lambda: model_lib.init_params(arch, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(serve.init_cache)
    tok = jax.ShapeDtypeStruct((2, 1), jnp.int32)
    prompt = jax.ShapeDtypeStruct((2, 4), jnp.int32)
    texts = [serve.decode_fn.lower(params, cache, tok, jnp.int32(4)),
             serve.prefill_fn.lower(params, cache, prompt)]
    for low in texts:
        assert stages_in(low.as_text(debug_info=True)) == {"serve"}


# ---------------------------------------------------------------------------
# the reduction attributes device operations to stages
# ---------------------------------------------------------------------------

_MS = 1_000_000   # ns


def _op(name, path, module="jit_seg"):
    return bench_stages.Op(name, module, path)


def _chip(scale: float):
    """One chip's operations inside a 100 ms window: a scan's ``while``
    spanning wrapped-scope leaves, and one operation with no stage."""
    body = "jit(_lambda_)/while/body/stream"
    ops = [
        (0, 10 * _MS * scale,
         _op("fusion.1", "jit(_lambda_)/transpose(jvp(grads))/dot_general")),
        (10 * _MS, 90 * _MS, _op("while.3", "jit(_lambda_)/while")),
        (10 * _MS, 14 * _MS, _op("sort.2", body + "/encode/vmap(threshold)/sort")),
        (14 * _MS, 20 * _MS,
         _op("ota_project.4", body + "/encode/vmap(jit(ota_project))"
             "/ota_project/pallas_call")),
        (20 * _MS, 80 * _MS, _op("amp_decode_fused.5",
                                 body + "/decode/jit(amp_decode_fused)")),
        (80 * _MS, 81 * _MS, _op("dynamic_update_slice.6",
                                 body + "/dynamic_update_slice")),
        (90 * _MS, 95 * _MS, _op("fusion.7", "jit(decode)/add")),
        (95 * _MS, 96 * _MS, _op("copy.8", "")),
    ]
    return sorted(ops)


def _synthetic():
    return bench_stages.ScopedTrace(device_ops={0: _chip(1.0), 1: _chip(1.5)},
                                    program_spans=[], route="tf_op")


@pytest.mark.parametrize("stage,per_chip_ms", [
    ("grads", (10.0, 15.0)),                    # transpose(jvp(grads))
    ("threshold", (4.0, 4.0)),                  # vmap(threshold)
    ("encode", (10.0, 10.0)),                   # threshold nests inside
    ("decode", (60.0, 60.0)),                   # jit(decode) is not a scope
    ("stream", (71.0, 71.0)),                   # the while is not counted
])
def test_stage_seconds_reads_wrapped_scopes(stage, per_chip_ms):
    units = 2
    got = bench_stages.stage_seconds(_synthetic(), stage, 0, 100 * _MS,
                                     units)
    want = sum(per_chip_ms) / 2 * 1e-3 / units
    assert got == pytest.approx(want)


def test_stage_seconds_is_none_for_an_absent_stage():
    st = _synthetic()
    assert bench_stages.stage_seconds(st, "serve", 0, 100 * _MS, 1) is None
    assert bench_stages.stage_seconds(st, "optimizer", 0, 100 * _MS,
                                      1) is None
    # present in the trace but outside the window
    assert bench_stages.stage_seconds(st, "decode", 85 * _MS, 100 * _MS,
                                      1) is None


def test_partition_counts_each_leaf_once():
    st = _synthetic()
    part = bench_stages.partition(st, 0, 100 * _MS, 1)
    assert part["stream"] == pytest.approx(1e-3)
    assert part["threshold"] == pytest.approx(4e-3)
    assert part["encode"] == pytest.approx(6e-3)
    assert part["grads"] == pytest.approx((10 + 15) / 2 * 1e-3)
    assert part["none"] == pytest.approx(6e-3)
    leaves = sum(e - s for ops in st.device_ops.values()
                 for s, e, op in ops if op.name != "while.3")
    assert sum(part.values()) == pytest.approx(leaves * 1e-9 / 2)


# ---------------------------------------------------------------------------
# host spans and the compile counter
# ---------------------------------------------------------------------------


def test_program_loops_emit_host_spans(mnist_like, tmp_path):
    xd, yd, xte, yte = mnist_like
    cfg = OTAConfig(scheme="a_dsgd", s_frac=0.5, k_frac=0.25, p_avg=500.0,
                    total_steps=4, projection="dense", amp_iters=3)
    ce = CompiledExperiment(xd, yd, xte, yte,
                            Experiment(cfg=cfg, steps=4, lr=1e-3))
    ckpt = str(tmp_path / "ckpt")
    log_dir = str(tmp_path / "trace")
    arch = get_config("smollm_360m").reduced()
    ota = OTAConfig(projection="blocked", s_frac=0.25, k_frac=0.5,
                    block_size=256)
    with jax.profiler.trace(log_dir):
        assert run_checkpointed(ce, {}, round_keys(4, 0),
                                checkpoint_dir=ckpt, checkpoint_every=2,
                                stop_after_step=2) is None
        run_checkpointed(ce, {}, round_keys(4, 0), checkpoint_dir=ckpt,
                         checkpoint_every=2, resume=True)
        serve_while_train(arch, rounds=1, ota=ota, m=2, seq_len=8,
                          decode_steps=2, checkpoint_dir=ckpt,
                          checkpoint_every=1)
    names = {s[2] for s in bench_stages.load(log_dir).program_spans}
    assert names == {"repro:" + n for n in (
        "segment", "checkpoint", "save_checkpoint", "load_checkpoint",
        "round", "publish", "verify_publish", "serve")}


def test_compile_counter_counts_backend_compiles(tmp_path):
    snippet = r"""
import json, jax, jax.numpy as jnp
from repro.launch.cache import enable_compile_cache
from repro.tracing import stage
path, counts = enable_compile_cache()
before = dict(counts)
jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(7)).block_until_ready()
jax.jit(lambda x: jnp.cos(x) + 1)(jnp.ones(5)).block_until_ready()
# the same program under another stage scope is not served the first
# build's executable (whose op names would carry the other scope)
jax.jit(stage("encode")(lambda x: jnp.tan(x)))(jnp.ones(3)).block_until_ready()
jax.jit(stage("decode")(lambda x: jnp.tan(x)))(jnp.ones(3)).block_until_ready()
print(json.dumps({"before": before, "after": counts}))
"""
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path), JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", snippet], capture_output=True,
                       text=True, timeout=240, env=env)
    assert r.returncode == 0, r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["before"]["backend_compiles"] == 0
    assert out["after"]["backend_compiles"] >= 4
    assert out["after"]["backend_compile_s"] > 0
    assert np.isfinite(out["after"]["backend_compile_s"])
    assert out["after"]["hits"] == 0    # the persistent-cache counts stay
    assert out["after"]["misses"] >= 4
