"""Observability layer (DESIGN.md §10): MetricSet pytree semantics, the
norm helpers, host span timing, the schema-versioned sink (round-trip +
validation over the committed history), and the guard invariants —
metrics-on and spans-on are value-bit-equal to the plain step, the
everything-off arm lowers with zero span metadata, and with
trace_spans=True every phase, sub-phase and wire-stage name reaches the
lowered module as op metadata."""
import glob
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ShapeSpec
from repro.core.muon import EF21Muon, EF21MuonConfig, ParamMeta
from repro.data import SyntheticLM
from repro.dist.layerwise import LayerPlan
from repro.models.api import build_model
from repro.obs.metrics import (MetricSet, leaf_names, orth_residual,
                               rel_error, worker_mean_norm)
from repro.obs.sink import (SCHEMA, MetricsWriter, SchemaError, config_hash,
                            run_manifest, validate_bench_file,
                            validate_jsonl, validate_record,
                            write_bench_artifact)
from repro.obs.trace import (PHASE_SPANS, SUB_SPANS, SpanRecorder,
                             phase_span, span, span_summary, sub_span,
                             wire_stage_span)
from repro.train.trainer import Trainer, TrainerConfig

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _tree(key):
    """The test_pipeline fixture: eager (sign) leaves + three NS buckets."""
    ks = jax.random.split(key, 7)
    params = {
        "wq": jax.random.normal(ks[0], (48, 32)),
        "wk": jax.random.normal(ks[1], (48, 32)),
        "w_in": jax.random.normal(ks[2], (32, 80)),
        "w_out": jax.random.normal(ks[3], (80, 32)),
        "blocks": jax.random.normal(ks[4], (3, 48, 32)),
        "tiny": jax.random.normal(ks[5], (16, 16)),
        "bias": jax.random.normal(ks[6], (32,)),
    }
    metas = {
        "wq": ParamMeta("spectral", 1.0, 0),
        "wk": ParamMeta("spectral", 1.0, 0),
        "w_in": ParamMeta("spectral", 1.5, 0),
        "w_out": ParamMeta("spectral", 1.0, 0),
        "blocks": ParamMeta("spectral", 2.0, 1),
        "tiny": ParamMeta("spectral", 1.0, 0),
        "bias": ParamMeta("sign", 1.0, 0, compressible=False),
    }
    return params, metas


def _quadratic_grad(params, batch):
    loss = sum(jnp.sum(jnp.square(p.astype(jnp.float32) - batch))
               for p in jax.tree.leaves(params))
    grads = jax.tree.map(
        lambda p: 2.0 * (p.astype(jnp.float32) - batch), params)
    return loss, grads


def _opt(**cfg_kw):
    return EF21Muon(EF21MuonConfig(n_workers=2, beta=0.5,
                                   w2s="top10+natural", s2w="natural",
                                   use_pallas=False, **cfg_kw))


def _run_steps(params, metas, key, n=3, **cfg_kw):
    opt = _opt(**cfg_kw)
    state = opt.init(key, params, metas)
    fn = opt.make_step(metas, reshard_payloads=lambda t: t)
    step = jax.jit(lambda s, b, t, f=fn: f(s, _quadratic_grad, b, t))
    for _ in range(n):
        state, aux = step(state, jnp.ones((2, 1)) * 0.1, 0.01)
    assert np.isfinite(float(aux["loss"]))
    return state, aux


# ----------------------------------------------------------- MetricSet

def test_metricset_pytree_roundtrip():
    ms = MetricSet()
    ms.add("ef/err_norm/a", 1.5)
    ms.add("wire/bytes_w2s", 42.0)
    assert ms.names() == ("ef/err_norm/a", "wire/bytes_w2s")
    assert len(ms) == 2 and "ef/err_norm/a" in ms
    doubled = jax.tree.map(lambda v: 2 * v, ms)
    assert isinstance(doubled, MetricSet)
    assert doubled.names() == ms.names()           # names ride the treedef
    assert float(doubled["ef/err_norm/a"]) == 3.0
    # survives a jit boundary as an output pytree
    out = jax.jit(lambda m: jax.tree.map(lambda v: v + 1, m))(ms)
    assert out.names() == ms.names()
    hf = out.host_floats()
    assert hf == {"ef/err_norm/a": 2.5, "wire/bytes_w2s": 43.0}
    assert all(isinstance(v, float) for v in hf.values())


def test_metricset_rejects_bad_and_duplicate_names():
    ms = MetricSet()
    ms.add("ok/name", 1.0)
    with pytest.raises(ValueError):
        ms.add("ok/name", 2.0)                     # duplicate
    for bad in ("", "a b", "a//b", "/lead", "trail/", "a\nb"):
        with pytest.raises(ValueError):
            ms.add(bad, 0.0)


def test_norm_helpers(key):
    x = jax.random.normal(key, (2, 5, 7))
    got = worker_mean_norm(x)
    want = np.mean([np.linalg.norm(np.asarray(x[j])) for j in range(2)])
    np.testing.assert_allclose(float(got), want, rtol=1e-6)
    # lead=0: one global F-norm
    np.testing.assert_allclose(float(worker_mean_norm(x, lead=0)),
                               np.linalg.norm(np.asarray(x).ravel()),
                               rtol=1e-6)
    # rel_error: ratio per worker, and 0 (not nan/inf) on a zero target
    r = rel_error(x, 2.0 * x)
    np.testing.assert_allclose(float(r), 0.5, rtol=1e-6)
    assert float(rel_error(x, jnp.zeros_like(x))) == 0.0


def test_orth_residual():
    # orthogonal rows -> residual 0; doubling them -> ||4I - I||_F = 3*sqrt(k)
    q = jnp.eye(4)[None, :3, :]                     # [1, 3, 4], QQ^T = I_3
    np.testing.assert_allclose(float(orth_residual(q)), 0.0, atol=1e-6)
    np.testing.assert_allclose(float(orth_residual(2.0 * q)),
                               3.0 * np.sqrt(3.0), rtol=1e-6)
    # tall input uses the column gram (smaller side)
    tall = jnp.eye(4)[None, :, :2]                  # [1, 4, 2], D^T D = I_2
    np.testing.assert_allclose(float(orth_residual(tall)), 0.0, atol=1e-6)


def test_leaf_names():
    tree = {"a": {"b": 1.0}, "c": [2.0, 3.0], "d w": 4.0}
    assert leaf_names(tree) == ("a/b", "c/0", "c/1", "d-w")
    assert leaf_names({}) == ()
    # flatten order matches treedef order (the metric <-> leaf contract)
    leaves, _ = jax.tree.flatten(tree)
    assert len(leaves) == len(leaf_names(tree))


# ---------------------------------------------------------- trace spans

def test_wire_stage_span_names():
    assert wire_stage_span("w2s", 0) == "wire/w2s/stage0"
    assert wire_stage_span("s2w", 3) == "wire/s2w/stage3"
    with pytest.raises(ValueError):
        wire_stage_span("up", 0)


def test_span_recorder_and_timer():
    rec = SpanRecorder()
    with span("t/outer", recorder=rec):
        time.sleep(0.01)
        with span("t/inner", recorder=rec):
            pass
    with span("t/inner", recorder=rec):
        pass
    rows = span_summary(rec)
    by_name = {r["name"]: r for r in rows}
    # rows in completion order: nested spans record on exit
    assert [r["name"] for r in rows] == ["t/inner", "t/outer"]
    assert by_name["t/inner"]["count"] == 2
    assert by_name["t/outer"]["total_s"] >= 0.01
    assert by_name["t/outer"]["max_s"] <= by_name["t/outer"]["total_s"] + 1e-9
    rec.clear()
    assert span_summary(rec) == []
    # span rows are valid sink records as-is
    for r in rows:
        validate_record({"schema": SCHEMA, "kind": "span", **r})


def test_phase_span_is_reentrant_under_trace():
    # graph arm inside a trace: named_scope must accept the names
    @jax.jit
    def f(x):
        with phase_span(PHASE_SPANS[0], True):
            with phase_span(wire_stage_span("w2s", 1), True):
                return x * 2
    assert float(f(jnp.float32(3.0))) == 6.0


def test_sub_span_scopes_only_inside_a_graph_phase():
    def names(fn):
        return str(jax.make_jaxpr(fn)(jnp.float32(1.0)).eqns[0]
                   .source_info.name_stack)

    def bare(x):
        with sub_span("ef.select"):
            return x * 2

    def in_phase(graph):
        def f(x):
            with phase_span(PHASE_SPANS[2], graph):
                return bare(x)
        return f

    assert names(bare) == ""
    assert names(in_phase(False)) == ""
    assert names(in_phase(True)) == f"{PHASE_SPANS[2]}/ef.select"
    assert names(bare) == ""      # the phase's flag does not leak out


# ------------------------------------------------- step guard invariants

def test_metrics_on_bit_equal_and_content(key):
    """The §10 acceptance pair: metrics-on produces the identical state
    bits, and aux["metrics"] carries the full taxonomy with sane values."""
    params, metas = _tree(key)
    base, _ = _run_steps(params, metas, key, wire_stages="auto")
    got, aux = _run_steps(params, metas, key, wire_stages="auto",
                          metrics=True)
    same = jax.tree.map(lambda a, b: bool(jnp.all(a == b)), got, base)
    assert all(jax.tree.leaves(same)), same

    ms = aux["metrics"]
    assert isinstance(ms, MetricSet)
    vals = ms.host_floats()
    assert all(np.isfinite(v) for v in vals.values()), vals
    for leaf in leaf_names(params):
        assert f"ef/err_norm/{leaf}" in ms
        assert f"ef/rel_err/{leaf}" in ms
        assert f"ef/momentum_norm/{leaf}" in ms
        assert f"efp/err_norm/{leaf}" in ms        # s2w="natural" engaged
    # the incompressible identity leaf has zero EF error by construction
    assert vals["ef/err_norm/bias"] == 0.0
    assert vals["ef/rel_err/bias"] == 0.0
    # TopK leaves count the kept entries tied at the threshold: at least
    # one a slice, at most all k; the identity leaf has no such count
    plan = LayerPlan.build(params, metas, w2s="top10+natural",
                           s2w="natural", use_pallas=False)
    for leaf, lp in zip(leaf_names(params), plan.leaves):
        if leaf == "bias":
            assert "ef/topk_ties/bias" not in ms
            continue
        k = lp.w2s.inner.k_for(lp.slice_shape)
        assert lp.n_stack <= vals[f"ef/topk_ties/{leaf}"] <= k * lp.n_stack
    # static wire accounting matches the layouts exactly
    dt = _opt().cfg.wire_dtype
    assert vals["wire/bytes_w2s"] == plan.wire_layout(dt).total_nbytes
    assert vals["wire/bytes_s2w"] == \
        plan.wire_layout(dt, direction="s2w").total_nbytes
    n_stages = plan.stage_plan().n_stages
    assert vals["wire/n_stages"] == n_stages
    # one NS residual per bucket, all strictly positive (finite chains)
    res = [n for n in ms.names() if n.startswith("ns/orth_residual/")]
    assert len(res) == len(plan.ns_buckets())
    assert all(vals[n] > 0 for n in res)
    # step rows built from these metrics validate against the sink schema
    validate_record({"schema": SCHEMA, "kind": "step", "step": 3,
                     "loss": 1.0, "metrics": vals})


def test_trace_spans_bit_equal(key):
    """trace_spans=True changes op metadata only — never the values."""
    params, metas = _tree(key)
    for ws in ("auto", 1):
        base, _ = _run_steps(params, metas, key, wire_stages=ws)
        got, _ = _run_steps(params, metas, key, wire_stages=ws,
                            trace_spans=True)
        same = jax.tree.map(lambda a, b: bool(jnp.all(a == b)), got, base)
        assert all(jax.tree.leaves(same)), (ws, same)
    # and on a transformer Trainer step, where the sub-phases scope the
    # model's blocks, head, TopK and the EF21 update
    base = _lm_steps(key, trace_spans=False)
    got = _lm_steps(key, trace_spans=True)
    same = jax.tree.map(lambda a, b: bool(jnp.all(a == b)), got, base)
    assert all(jax.tree.leaves(same)), same


def _lowered_debug_text(params, metas, key, **cfg_kw):
    """Lowered module printed WITH debug locations — where named_scope
    lands before any fusion can merge ops away (compiled-HLO op_name
    metadata drops scopes whose ops fuse, e.g. the tiny eager stage)."""
    import io
    opt = _opt(wire_stages="auto", **cfg_kw)
    state = opt.init(key, params, metas)
    fn = opt.make_step(metas, reshard_payloads=lambda t: t)
    step = jax.jit(lambda s, b, t, f=fn: f(s, _quadratic_grad, b, t))
    low = step.lower(state, jnp.ones((2, 1)) * 0.1,
                     jnp.asarray(0.01, jnp.float32))
    buf = io.StringIO()
    low.compiler_ir().operation.print(file=buf, enable_debug_info=True)
    return buf.getvalue()


def test_span_metadata_gated_by_trace_spans(key):
    """The HLO-identity boundary: with everything off, no span name
    reaches the lowered module (same guard style as the §8
    wire_stages=1 arm — the default build must not know obs exists);
    with trace_spans=True every phase + wire-stage name is op metadata."""
    params, metas = _tree(key)
    off = _lowered_debug_text(params, metas, key)
    for name in PHASE_SPANS:
        assert name not in off
    assert "wire/w2s/stage" not in off and "wire/s2w/stage" not in off

    on = _lowered_debug_text(params, metas, key, trace_spans=True)
    n_stages = LayerPlan.build(params, metas, w2s="top10+natural",
                               s2w="natural",
                               use_pallas=False).stage_plan().n_stages
    for name in PHASE_SPANS:
        assert name in on, name
    for k in range(n_stages):
        assert wire_stage_span("w2s", k) in on
        assert wire_stage_span("s2w", k) in on


def _lm_trainer(trace_spans):
    """A two-layer nanoGPT Trainer on top10+natural messages, the
    benchmark's compressed arm at a CPU size."""
    cfg = get_config("nanogpt-124m").reduced()
    tr = Trainer(build_model(cfg), TrainerConfig(
        n_workers=2, beta=0.5, w2s="top10+natural", remat=True,
        use_pallas=False, trace_spans=trace_spans))
    data = SyntheticLM(cfg, ShapeSpec("t", "train", 32, 4), n_workers=2,
                       seed=0)
    return tr, data


def _lm_steps(key, n=2, **tcfg_kw):
    tr, data = _lm_trainer(**tcfg_kw)
    state = tr.init(key)
    step = jax.jit(tr.make_step())
    for i in range(n):
        state, aux = step(state, data.batch_at(i), 0.01)
    assert np.isfinite(float(aux["loss"]))
    return state


def _lm_debug_text(key, trace_spans):
    import io
    tr, data = _lm_trainer(trace_spans)
    low = tr.jit_step(None).lower(tr.init(key), data.batch_at(0),
                                  jnp.asarray(0.01, jnp.float32))
    buf = io.StringIO()
    low.compiler_ir().operation.print(file=buf, enable_debug_info=True)
    return buf.getvalue()


@pytest.fixture(scope="module")
def lm_lowered():
    """The transformer step lowered off, on, off in one process: a trace
    cached by one arm (a scan body, say) and reused by the next would
    carry the wrong arm's names."""
    key = jax.random.key(0)
    return [_lm_debug_text(key, spans) for spans in (False, True, False)]


@pytest.mark.parametrize("name", SUB_SPANS)
def test_sub_span_metadata_gated_by_trace_spans(lm_lowered, name):
    off, on, off_again = lm_lowered
    assert name in on
    assert name not in off and name not in off_again
    assert off_again == off


# ----------------------------------------------------------------- sink

def test_metrics_writer_roundtrip(tmp_path):
    path = str(tmp_path / "m.jsonl")
    manifest = run_manifest(config={"beta": 0.5}, mesh=None,
                            extra={"arch": "t"})
    with MetricsWriter(path, manifest=manifest, flush_every=2) as w:
        w.write("step", step=0, loss=jnp.float32(3.25),
                metrics={"ef/err_norm/a": jnp.float32(0.5)})
        w.write("step", step=1, loss=1.0)
        w.write("span", name="plan/build", count=1, total_s=0.01)
        w.write("summary", spans=[{"name": "plan/build", "count": 1,
                                   "total_s": 0.01}])
    counts = validate_jsonl(path)
    assert counts == {"manifest": 1, "step": 2, "span": 1, "summary": 1}
    recs = [json.loads(line) for line in open(path)]
    assert all(r["schema"] == SCHEMA for r in recs)
    assert recs[0]["kind"] == "manifest"
    assert recs[0]["config_hash"] == config_hash({"beta": 0.5})
    assert recs[0]["arch"] == "t" and "argv" in recs[0]
    # jax scalars landed as plain JSON numbers
    assert recs[1]["loss"] == 3.25
    assert recs[1]["metrics"]["ef/err_norm/a"] == 0.5


def test_metrics_writer_append_resume(tmp_path):
    path = str(tmp_path / "d.jsonl")
    row = dict(arch="a", shape="s", mesh="single", tag="t", status="ok")
    with MetricsWriter(path, append=True) as w:
        w.write_record({"kind": "dryrun", **row})
    with MetricsWriter(path, append=True) as w:     # resume: no rewrite
        w.write_record({"kind": "dryrun", **row, "tag": "t2"})
    assert validate_jsonl(path) == {"dryrun": 2}
    tags = [json.loads(line)["tag"] for line in open(path)]
    assert tags == ["t", "t2"]


def test_writer_rejects_bad_records(tmp_path):
    with MetricsWriter(str(tmp_path / "x.jsonl")) as w:
        with pytest.raises(SchemaError):
            w.write("step", step=0)                 # missing loss
        with pytest.raises(SchemaError):
            w.write("nope", a=1)                    # unknown kind
        with pytest.raises(SchemaError):
            w.write("step", step=0, loss=1.0,
                    metrics={"a": "not-a-number"})
    assert validate_jsonl(str(tmp_path / "x.jsonl")) == {}


def test_validate_record_legacy_and_envelope():
    # legacy bench row: "kind" is a domain field, not the discriminator
    assert validate_record({"bench": "ns", "kind": "dispatch"}) == "bench"
    # legacy dryrun row (the committed pre-v1 shape)
    assert validate_record({"arch": "a", "shape": "s", "mesh": "m",
                            "tag": "t", "status": "ok"}) == "dryrun"
    # enveloped records enforce the discriminator + schema version
    with pytest.raises(SchemaError):
        validate_record({"schema": SCHEMA, "kind": "step", "bench": "x"},
                        kind="bench")               # kind mismatch
    with pytest.raises(SchemaError):
        validate_record({"schema": "repro.metrics/v0", "kind": "bench",
                         "bench": "x"})
    with pytest.raises(SchemaError):
        validate_record({"mystery": 1})             # uninferrable
    with pytest.raises(SchemaError):
        validate_record({"schema": SCHEMA, "kind": "step", "step": "0",
                         "loss": 1.0})              # step must be int


def test_validate_committed_history():
    """The committed sink files all pass the v1 validator: the dry-run
    log (legacy + new rows) and every BENCH_*.json artifact."""
    counts = validate_jsonl(os.path.join(REPO, "results/dryrun.jsonl"))
    assert counts.get("dryrun", 0) > 0, counts
    benches = glob.glob(os.path.join(REPO, "BENCH_*.json"))
    assert benches
    for p in benches:
        assert validate_bench_file(p) > 0, p


def test_write_bench_artifact_validates(tmp_path):
    path = str(tmp_path / "BENCH_t.json")
    rows = [{"bench": "t", "value": 1}, {"bench": "t", "value": 2}]
    write_bench_artifact(path, "t", rows, fast=True)
    assert validate_bench_file(path) == 2
    doc = json.load(open(path))
    assert doc["bench"] == "t" and doc["fast"] is True
    with pytest.raises(SchemaError):
        write_bench_artifact(path, "t", [{"value": 3}])   # no bench key
