"""repro.analysis (DESIGN.md §12): the lint engine's unit surface.

Seeded-violation coverage: each rule gets a deliberately broken
hand-written module (extra collective, forced upcast, dropped donation,
replicated bucket dot, host callback, drifted hash) and the assertion is
two-sided — the violation trips *its* rule, and no other rule
(error/warn level) fires on the same artifact. The lint CLI itself is
exercised end-to-end by the slow matrix test and CI's lint job.
"""
import json
import os
import subprocess
import sys

import pytest

from repro.analysis import hlo_ir
from repro.analysis.baseline import (hashes_comparable, load_baseline,
                                     save_baseline)
from repro.analysis.program import (BucketAudit, ProgramArtifact,
                                    canonical_hash, entry_param_bytes,
                                    input_output_aliases)
from repro.analysis.rules import (RULES, equality_findings, run_rules,
                                  wire_budget_findings)
from repro.core.muon import WireBudget
from repro.launch.hlo_analysis import attribute_u8_directions
from repro.launch.hlo_cost import analyze


def _module(body_lines, header="", extra_comps=""):
    body = "\n".join("  " + ln for ln in body_lines)
    return (f"HloModule m{header}\n\n{extra_comps}"
            f"ENTRY main {{\n{body}\n}}\n")


def _hard(findings):
    """error/warn findings only — the levels that fail the lint."""
    return [f for f in findings if f.level in ("error", "warn")]


# ------------------------------------------------------- hlo_ir re-exports

def test_hlo_cost_reexports_shared_ir():
    """Satellite: launch.hlo_cost's parser IS analysis.hlo_ir (one
    parser, two consumers — no drift possible)."""
    from repro.launch import hlo_cost

    assert hlo_cost.parse_module is hlo_ir.parse_module
    assert hlo_cost.Computation is hlo_ir.Computation
    assert hlo_cost.Instr is hlo_ir.Instr


# a CPU layout, and a TPU tiled layout whose parentheses the type must take
@pytest.mark.parametrize("layout", ["{0}", "{0:T(1024)(128)(4,1)}"])
def test_parse_handwritten_module(layout):
    comps = hlo_ir.parse_module(_module([
        f"p0 = u8[1024]{layout} parameter(0)",
        f"ag = u8[4096]{layout} all-gather(p0), dimensions={{0}}",
        f"ROOT c = u8[1024]{layout} copy(p0)",
    ]))
    entry = hlo_ir.entry_name(comps)
    comp = comps[entry]
    assert comp.sizes["p0"] == 1024
    assert comp.sizes["ag"] == 4096
    assert [hlo_ir.base_op(i.op) for i in comp.instrs] == \
        ["parameter", "all-gather", "copy"]


# ------------------------------------------------------- orphan regression

ORPHAN_HLO = _module([
    "p0 = u8[1024]{0} parameter(0)",
    "ags = (u8[1024]{0}, u8[4096]{0}) all-gather-start(p0), dimensions={0}",
    "ROOT c = u8[1024]{0} copy(p0)",
])


def test_orphan_gather_start_not_attributed():
    """Regression (satellite 2): an async all-gather-start whose -done
    is missing (truncated module text) used to window to the end of the
    computation and byte-match a direction as if it completed. It must
    surface as an orphan instead — unmatched, its expected size still
    missing."""
    pairs = analyze(ORPHAN_HLO)["coll_pairs"]
    assert len(pairs) == 1 and pairs[0]["orphan"] is True
    split = attribute_u8_directions(pairs, [1024], [])
    assert split["w2s"] == {"bytes": 0, "count": 0}
    assert split["missing"]["w2s"] == [1024]
    assert split["missing"]["orphan"] == [1024]
    budget = WireBudget(pack_w2s=True, pack_s2w=False, n_stages=1,
                        w2s_sizes=(1024,), s2w_sizes=())
    msgs = [f.message for f in wire_budget_findings(pairs, budget, "t")]
    assert any("without a matching done" in m for m in msgs), msgs


# ------------------------------------------------- seeded: wire-budget

def _wire_art(gather_operands, budget):
    lines = []
    for i, nbytes in enumerate(gather_operands):
        lines.append(f"p{i} = u8[{nbytes}]{{0}} parameter({i})")
        lines.append(f"ag{i} = u8[{nbytes * 4}]{{0}} all-gather(p{i}), "
                     "replica_groups={{0,1,2,3}}, dimensions={0}")
    lines.append("ROOT r = u8[8]{0} constant({0})")
    return ProgramArtifact(cell="seed", hlo_text=_module(lines),
                           budget=budget)


def test_seeded_extra_collective_trips_only_wire_budget():
    budget = WireBudget(pack_w2s=True, pack_s2w=False, n_stages=1,
                        w2s_sizes=(1024,), s2w_sizes=())
    # green path: exactly the budget's population -> no findings
    assert _hard(run_rules(_wire_art([1024], budget))) == []
    # seeded: one extra u8 all-gather nobody budgeted
    bad = _hard(run_rules(_wire_art([1024, 512], budget)))
    assert {f.rule for f in bad} == {"wire-budget"}
    assert any("no wire direction expects" in f.message for f in bad)


def test_seeded_missing_collective_trips_only_wire_budget():
    budget = WireBudget(pack_w2s=True, pack_s2w=False, n_stages=2,
                        w2s_sizes=(1024, 512), s2w_sizes=())
    bad = _hard(run_rules(_wire_art([1024], budget)))
    assert {f.rule for f in bad} == {"wire-budget"}
    assert any("1 u8 all-gathers byte-matched, expected 2" in f.message
               for f in bad)


# ------------------------------------------------- seeded: dtype-upcast

def test_seeded_u8_float_upcast_trips_only_dtype_rule():
    art = ProgramArtifact(cell="seed", hlo_text=_module([
        "p0 = u8[4096]{0} parameter(0)",
        "ROOT c = f32[4096]{0} convert(p0)",
    ]))
    bad = _hard(run_rules(art))
    assert {f.rule for f in bad} == {"dtype-upcast"}
    assert any("u8 -> f32" in f.message for f in bad)
    # small converts (indices, flags) stay legal
    ok = ProgramArtifact(cell="seed", hlo_text=_module([
        "p0 = u8[16]{0} parameter(0)",
        "ROOT c = f32[16]{0} convert(p0)",
    ]))
    assert _hard(run_rules(ok)) == []


def test_seeded_f64_trips_only_dtype_rule():
    art = ProgramArtifact(cell="seed", hlo_text=_module([
        "p0 = f32[64]{0} parameter(0)",
        "ROOT c = f64[64]{0} convert(p0)",
    ]))
    bad = _hard(run_rules(art))
    assert {f.rule for f in bad} == {"dtype-upcast"}
    assert any("f64" in f.message for f in bad)


def test_seeded_state_dtype_drift_trips_only_dtype_rule():
    art = ProgramArtifact(
        cell="seed",
        hlo_text=_module(["ROOT p0 = bf16[64]{0} parameter(0)"]),
        state_in=(("['x']", (64,), "bfloat16"),),
        state_out=(("['x']", (64,), "float32"),))
    bad = _hard(run_rules(art))
    assert {f.rule for f in bad} == {"dtype-upcast"}
    assert any("drifts bfloat16 -> float32" in f.message for f in bad)


# ---------------------------------------------------- seeded: donation

_DONATE_LINES = [
    "p0 = f32[16384,16]{1,0} parameter(0)",   # 1 MiB state leaf
    "p1 = f32[16384,16]{1,0} parameter(1)",   # 1 MiB state leaf
    "p2 = f32[8]{0} parameter(2)",            # batch
    "ROOT t = (f32[16384,16]{1,0}, f32[16384,16]{1,0}) tuple(p0, p1)",
]
_STATE2 = ((" ['x']", (16384, 16), "float32"),
           (" ['m']", (16384, 16), "float32"))


def test_seeded_dropped_donation_trips_only_donation_rule():
    # only leaf 1 aliased; leaf 0's MiB stays double-buffered
    art = ProgramArtifact(
        cell="seed",
        hlo_text=_module(
            _DONATE_LINES,
            header=", input_output_alias={ {1}: (1, {}, may-alias) }"),
        donate=True, state_in=_STATE2, state_out=_STATE2, n_flat_args=3)
    bad = _hard(run_rules(art))
    assert {f.rule for f in bad} == {"donation"}
    assert any("not input/output aliased" in f.message for f in bad)
    # green path: both large leaves aliased
    ok = ProgramArtifact(
        cell="seed",
        hlo_text=_module(
            _DONATE_LINES,
            header=", input_output_alias={ {0}: (0, {}, may-alias), "
                   "{1}: (1, {}, may-alias) }"),
        donate=True, state_in=_STATE2, state_out=_STATE2, n_flat_args=3)
    assert _hard(run_rules(ok)) == []


def test_alias_and_param_parsers():
    text = _module(
        _DONATE_LINES,
        header=", input_output_alias={ {0}: (0, {}, may-alias), "
               "{1}: (1, {}, may-alias) }")
    assert input_output_aliases(text) == {0, 1}
    pb = entry_param_bytes(hlo_ir.parse_module(text))
    assert pb == {0: 16384 * 16 * 4, 1: 16384 * 16 * 4, 2: 32}


# ------------------------------------------------- seeded: replication

_NS_DOT = ("d{i} = f32[8,64,64]{{2,1,0}} dot(x{i}, x{i}), "
           "lhs_contracting_dims={{2}}, rhs_contracting_dims={{1}}")
_BUCKET = BucketAudit((8, 64, 64), (2, 64, 32),
                      "PartitionSpec('data', None, 'model')")


def test_seeded_replicated_bucket_dot_trips_only_replication():
    art = ProgramArtifact(cell="seed", hlo_text=_module([
        "x0 = f32[8,64,64]{2,1,0} parameter(0)",
        _NS_DOT.format(i=0),
        "ROOT r = f32[8,64,64]{2,1,0} copy(d0)",
    ]), buckets=(_BUCKET,))
    bad = _hard(run_rules(art))
    assert {f.rule for f in bad} == {"replication"}
    assert any("materialises full NS bucket stack 8x64x64" in f.message
               for f in bad)
    # the per-device shard is NOT a violation
    ok = ProgramArtifact(cell="seed", hlo_text=_module([
        "x0 = f32[2,64,32]{2,1,0} parameter(0)",
        "d0 = f32[2,64,64]{2,1,0} dot(x0, x0), "
        "lhs_contracting_dims={2}, rhs_contracting_dims={2}",
        "ROOT r = f32[2,64,64]{2,1,0} copy(d0)",
    ]), buckets=(_BUCKET,))
    assert _hard(run_rules(ok)) == []


def test_replication_ignores_while_bodies():
    """The model's scan-over-layers may legitimately contain dots whose
    dims collide with a bucket stack; the walk stops at whiles."""
    extra = (
        "body {\n"
        "  bp = (f32[8,64,64]{2,1,0}) parameter(0)\n"
        "  bx = f32[8,64,64]{2,1,0} get-tuple-element(bp), index=0\n"
        "  bd = f32[8,64,64]{2,1,0} dot(bx, bx), "
        "lhs_contracting_dims={2}, rhs_contracting_dims={1}\n"
        "  ROOT br = (f32[8,64,64]{2,1,0}) tuple(bd)\n"
        "}\n\n"
        "cond {\n"
        "  cp = (f32[8,64,64]{2,1,0}) parameter(0)\n"
        "  ROOT cc = pred[] constant(false)\n"
        "}\n\n")
    art = ProgramArtifact(cell="seed", hlo_text=_module([
        "p0 = (f32[8,64,64]{2,1,0}) parameter(0)",
        "ROOT w = (f32[8,64,64]{2,1,0}) while(p0), condition=cond, "
        "body=body",
    ], extra_comps=extra), buckets=(_BUCKET,))
    assert _hard(run_rules(art)) == []


# --------------------------------------------------- seeded: host-sync

def test_seeded_host_callback_trips_only_host_sync():
    art = ProgramArtifact(cell="seed", hlo_text=_module([
        "p0 = f32[4]{0} parameter(0)",
        'ROOT cc = f32[4]{0} custom-call(p0), '
        'custom_call_target="xla_python_cpu_callback"',
    ]))
    bad = _hard(run_rules(art))
    assert {f.rule for f in bad} == {"host-sync"}
    # device custom-calls (deepseek's TopK) are not host round-trips
    ok = ProgramArtifact(cell="seed", hlo_text=_module([
        "p0 = f32[4]{0} parameter(0)",
        'ROOT cc = f32[4]{0} custom-call(p0), custom_call_target="TopK"',
    ]))
    assert _hard(run_rules(ok)) == []


def test_seeded_outfeed_trips_host_sync():
    art = ProgramArtifact(cell="seed", hlo_text=_module([
        "p0 = f32[4]{0} parameter(0)",
        "tok = token[] after-all()",
        "ROOT of = token[] outfeed(p0, tok)",
    ]))
    bad = _hard(run_rules(art))
    assert {f.rule for f in bad} == {"host-sync"}


# ----------------------------------------------- seeded: lowering-drift

def test_seeded_hash_drift_trips_only_drift_rule():
    art = ProgramArtifact(cell="c", hlo_text=_module(
        ["ROOT p0 = f32[4]{0} parameter(0)"]))
    ctx = {"baseline_hashes": {"c": "0" * 16}, "hashes_comparable": True}
    bad = _hard(run_rules(art, ctx))
    assert {f.rule for f in bad} == {"lowering-drift"}
    # a jax-version mismatch gates the comparison off
    ctx["hashes_comparable"] = False
    assert _hard(run_rules(art, ctx)) == []
    # matching hash: clean
    ctx = {"baseline_hashes": {"c": art.canonical_hash},
           "hashes_comparable": True}
    assert _hard(run_rules(art, ctx)) == []


def test_canonical_hash_mods_out_ssa_names_and_metadata():
    # real dumps %-prefix every value name; uniquifier suffixes and op
    # metadata (source paths!) must not affect the fingerprint
    a = _module(['%x.1 = f32[4]{0} add(%a.2, %b.3), metadata={op_name="f" '
                 'source_file="/tmp/a.py" source_line=3}',
                 "ROOT %r.4 = f32[4]{0} copy(%x.1)"])
    b = _module(["%y.9 = f32[4]{0} add(%c.7, %d.8)",
                 "ROOT %q.5 = f32[4]{0} copy(%y.9)"])
    assert canonical_hash(a) == canonical_hash(b)
    c = _module(["%y.9 = f32[4]{0} multiply(%c.7, %d.8)",
                 "ROOT %q.5 = f32[4]{0} copy(%y.9)"])
    assert canonical_hash(a) != canonical_hash(c)
    # operand-order swaps survive the renaming (first-appearance order)
    d = _module(["%y.9 = f32[4]{0} add(%d.8, %c.7)",
                 "ROOT %q.5 = f32[4]{0} copy(%y.9)"])
    assert canonical_hash(b) == canonical_hash(d)  # args unseen before
    e = _module(["%u = f32[4]{0} negate(%c.7)",
                 "%y.9 = f32[4]{0} add(%d.8, %c.7)",
                 "ROOT %q.5 = f32[4]{0} copy(%y.9)"])
    f = _module(["%u = f32[4]{0} negate(%c.7)",
                 "%y.9 = f32[4]{0} add(%c.7, %d.8)",
                 "ROOT %q.5 = f32[4]{0} copy(%y.9)"])
    assert canonical_hash(e) != canonical_hash(f)  # a real operand swap


def test_equality_findings():
    a = ProgramArtifact(cell="a", hlo_text=_module(
        ["ROOT p0 = f32[4]{0} parameter(0)"]))
    b = ProgramArtifact(cell="b", hlo_text=_module(
        ["ROOT p0 = f32[8]{0} parameter(0)"]))
    same = ProgramArtifact(cell="a2", hlo_text=a.hlo_text)
    assert equality_findings(a, same) == []
    diff = equality_findings(a, b)
    assert len(diff) == 1 and diff[0].rule == "lowering-drift"
    assert diff[0].cell == "a~b"


# -------------------------------------------------------- budget + sink

def test_wire_budget_matches_layer_plan_accounts():
    """WireBudget's per-stage sizes must reproduce the monolithic
    WireLayout byte accounts (both directions), with one entry per
    stage — the budget is a re-slicing of Table 2, not a new account."""
    from repro.configs import get_config
    from repro.core.muon import EF21Muon, EF21MuonConfig
    from repro.models.api import abstract_params, build_model

    cfg = get_config("nanogpt-124m").reduced()
    params, metas = abstract_params(build_model(cfg))
    opt = EF21Muon(EF21MuonConfig(n_workers=4, beta=0.5,
                                  w2s="top10+natural", s2w="natural",
                                  use_pallas=False))
    budget = opt.wire_budget(params, metas, distributed=True)
    plan = opt.plan(params, metas)
    dt = opt.cfg.wire_dtype
    assert budget.pack_w2s and budget.pack_s2w
    assert budget.w2s_nbytes == plan.wire_layout(dt).total_nbytes
    assert budget.s2w_nbytes == \
        plan.wire_layout(dt, direction="s2w").total_nbytes
    assert len(budget.w2s_sizes) == len(budget.s2w_sizes) \
        == budget.n_stages
    assert budget.two_way_nbytes == budget.w2s_nbytes + budget.s2w_nbytes
    # undistributed: no collectives expected in either direction
    local = opt.wire_budget(params, metas, distributed=False)
    assert local.w2s_sizes == () and local.s2w_sizes == ()


def test_sink_lint_kind():
    from repro.obs.sink import SchemaError, validate_record

    rec = {"schema": "repro.metrics/v1", "kind": "lint",
           "rule": "wire-budget", "cell": "nanogpt-124m@4x2/default",
           "level": "error", "message": "boom", "data": {"x": 1}}
    assert validate_record(rec) == "lint"
    with pytest.raises(SchemaError):
        validate_record({"schema": "repro.metrics/v1", "kind": "lint",
                         "rule": "wire-budget"})


def test_baseline_roundtrip(tmp_path):
    p = str(tmp_path / "b.json")
    doc = save_baseline(p, {"c": "abc"}, ["r|c|m"])
    assert load_baseline(p) == doc
    assert hashes_comparable(doc)       # recorded under the running jax
    doc["jax"] = "0.0.0"
    assert not hashes_comparable(doc)
    empty = load_baseline(str(tmp_path / "missing.json"))
    assert empty["hashes"] == {} and empty["findings"] == []


def test_rule_registry_complete():
    assert set(RULES) == {"wire-budget", "replication", "dtype-upcast",
                          "donation", "host-sync", "lowering-drift"}


# -------------------------------------------------------- CLI (slow)

@pytest.mark.slow
def test_lint_cli_end_to_end(tmp_path):
    """The CLI over one real cell: first run records the baseline
    (exit 0), the re-run reproduces hashes and findings against it
    (exit 0) — lowering determinism and the allowlist workflow in one.
    A second --update-baseline after the green run must keep the
    still-firing allowlist entries (regression: it used to save only
    *unbaselined* findings, so updating on green wiped the list)."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = "src"
    base = str(tmp_path / "baseline.json")
    cmd = [sys.executable, "-m", "repro.analysis.lint",
           "--configs", "nanogpt-124m", "--arms", "default",
           "--baseline", base]
    cwd = os.path.join(os.path.dirname(__file__), "..")
    out = subprocess.run(cmd + ["--update-baseline"], env=env, cwd=cwd,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr[-2000:]
    doc = json.load(open(base))
    assert doc["hashes"], doc
    out2 = subprocess.run(cmd, env=env, cwd=cwd, capture_output=True,
                          text=True, timeout=900)
    assert out2.returncode == 0, out2.stdout + out2.stderr[-2000:]
    out3 = subprocess.run(cmd + ["--update-baseline"], env=env, cwd=cwd,
                          capture_output=True, text=True, timeout=900)
    assert out3.returncode == 0, out3.stdout + out3.stderr[-2000:]
    doc3 = json.load(open(base))
    assert doc3["findings"] == doc["findings"], (doc, doc3)
    assert doc3["hashes"] == doc["hashes"]
