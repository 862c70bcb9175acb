"""hvdhlo suite (ISSUE 8 tentpole): compile-time lint of lowered XLA.

The golden StableHLO fixtures under ``tests/fixtures/hlo/`` are tiny
jitted programs lowered on CPU (regenerate with
``scripts/gen_hlo_fixtures.py``), so the per-rule tests are hermetic —
no lowering at test time. The acceptance tests DO lower live on the
conftest 8-device virtual mesh: the canonical `--hlo-step lm` program
must be clean under the default fusion config and must trip HVD201
when the pre-PR-6 single-giant-allreduce plan (64 MB threshold, cap
lifted) is reintroduced.
"""

import json
import os

import pytest

from horovod_tpu.analysis import hlo, hlo_rules
from horovod_tpu.analysis.driver import run_cli

HERE = os.path.dirname(__file__)
FIXDIR = os.path.join(HERE, "fixtures", "hlo")


def fixture_text(name):
    with open(os.path.join(FIXDIR, f"{name}.mlir"), encoding="utf-8") as f:
        return f.read()


def rules_of(findings):
    return sorted({f.rule_id for f in findings})


# ------------------------------------------------------------- parser

def test_parse_stablehlo_ops_and_types():
    prog = hlo.parse(fixture_text("hvd205_upcast_matmul"), "fx")
    assert prog.fmt == "stablehlo"
    conv = [op for op in prog.ops if op.opcode == "convert"]
    assert conv, "convert op not parsed"
    assert conv[0].operand_types[0].dtype == "bf16"
    assert conv[0].result_types[0].dtype == "f32"
    assert conv[0].result_types[0].dims == (128, 256)
    assert any(op.opcode == "dot_general" for op in prog.ops)


def test_parse_donation_survives_sharding_attr():
    """A donated arg whose attr dict ALSO carries an mhlo.sharding
    string (nested braces) must keep its donation bit — GSPMD dumps
    annotate both."""
    text = ('module @m {\n'
            '  func.func public @main(%arg0: tensor<2097152xf32> '
            '{jax.buffer_donor = true, mhlo.sharding = "{replicated}"}, '
            '%arg1: tensor<2097152xf32>) -> tensor<2097152xf32> {\n'
            '    %0 = stablehlo.add %arg0, %arg1 : tensor<2097152xf32>\n'
            '    return %0 : tensor<2097152xf32>\n'
            '  }\n'
            '}')
    prog = hlo.parse(text, "t")
    assert prog.entry_params[0].donated
    assert not prog.entry_params[1].donated
    assert [f.rule_id for f in hlo.lint_text(text)] == ["HVD203"]


def test_parse_stablehlo_entry_params_and_donation():
    prog = hlo.parse(fixture_text("hvd203_donated"), "fx")
    donated = [p for p in prog.entry_params if p.donated]
    assert len(donated) == 1 and donated[0].name == "%arg0"
    prog = hlo.parse(fixture_text("hvd203_undonated"), "fx")
    assert not any(p.donated for p in prog.entry_params)
    assert prog.entry_params[0].type.nbytes == 1024 * 1024 * 4


def test_parse_stablehlo_region_all_reduce_payload():
    """The region form ("stablehlo.all_reduce"(...) ({ ... })) carries
    its type on the closing line; payloads must still resolve."""
    prog = hlo.parse(fixture_text("hvd201_giant_allreduce"), "fx")
    ars = [op for op in prog.ops if op.opcode == "all_reduce"]
    assert ars, "no all_reduce parsed from the region form"
    payloads = [hlo_rules._collective_payload(op) for op in ars]
    assert all(p for p in payloads)
    # two ~8 MB weight gradients fused into one giant payload
    assert max(payloads) > 8 * 1024 * 1024


def test_parse_def_use_and_depends_on():
    prog = hlo.parse(fixture_text("hvd201_chained"), "fx")
    colls = sorted((op for op in prog.ops if op.opcode == "all_reduce"),
                   key=lambda o: o.line)
    assert len(colls) == 2
    assert prog.depends_on(colls[1], colls[0])
    assert not prog.depends_on(colls[0], colls[1])


def test_parse_hlo_text_compiled_module():
    """The OTHER textual form: a compiled (optimized, scheduled) module
    round-trips through the same rules — payloads, donation bits and
    parameters all resolve from HLO text."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x, w: jnp.tanh(x @ w), donate_argnums=(0,))
    x = jnp.ones((512, 512), jnp.float32)
    comp = f.lower(x, x).compile()
    prog = hlo.parse(comp.as_text(), "compiled")
    assert prog.fmt == "hlo"
    assert prog.entry_scope
    assert prog.entry_params, "entry parameters not parsed"
    assert any(p.donated for p in prog.entry_params)


# ------------------------------------------------- rule fixtures

#: fixture name -> rule set the analyzer must produce (the golden
#: contract: each positive flags exactly its rule; twins are clean).
FIXTURE_RULES = {
    "hvd201_giant_allreduce": ["HVD201"],
    "hvd201_bucketed": [],
    "hvd201_chained": ["HVD201"],
    "hvd202_host_callback": ["HVD202"],
    "hvd203_undonated": ["HVD203"],
    "hvd203_donated": [],
    "hvd204_resnet_block": ["HVD204"],
    "hvd204_resnet_block_padded": [],
    "hvd205_upcast_matmul": ["HVD205"],
    "hvd205_upcast_accum": [],
}


@pytest.mark.parametrize("name,expected", sorted(FIXTURE_RULES.items()))
def test_fixture_rules(name, expected):
    findings = hlo.lint_text(fixture_text(name), path=name)
    assert rules_of(findings) == expected, \
        [f.render() for f in findings]


def test_hvd201_payload_message_names_sizes():
    fs = hlo.lint_text(fixture_text("hvd201_giant_allreduce"))
    msg = [f for f in fs if f.rule_id == "HVD201"][0].message
    assert "MB" in msg and "bucket cap" in msg


def test_hvd201_serialized_chain_message():
    fs = hlo.lint_text(fixture_text("hvd201_chained"))
    assert "serialized dependency chain" in fs[0].message


def test_hvd201_env_limit_override(monkeypatch):
    """An explicit byte limit rules the payload check; a lifted bucket
    cap must NOT lift the limit (the regression scenario keeps
    gating)."""
    monkeypatch.setenv("HOROVOD_HLO_LINT_MAX_COLLECTIVE_BYTES",
                       str(1 << 30))
    assert not [f for f in hlo.lint_text(
        fixture_text("hvd201_giant_allreduce")) if f.rule_id == "HVD201"]
    monkeypatch.delenv("HOROVOD_HLO_LINT_MAX_COLLECTIVE_BYTES")
    monkeypatch.setenv("HOROVOD_BUCKET_CAP", "0")  # "lifted"
    assert [f for f in hlo.lint_text(
        fixture_text("hvd201_giant_allreduce")) if f.rule_id == "HVD201"]


def test_hvd203_min_bytes_floor(monkeypatch):
    monkeypatch.setenv("HOROVOD_HLO_LINT_MIN_DONATION_BYTES",
                       str(1 << 30))
    assert hlo.lint_text(fixture_text("hvd203_undonated")) == []


def test_hvd204_reports_waste_pct():
    fs = hlo.lint_text(fixture_text("hvd204_resnet_block"))
    assert any("50.0%" in f.message for f in fs)
    # channels 64: input + kernel i/o dims of both convs
    assert len(fs) >= 3


def test_hvd204_waste_threshold(monkeypatch):
    monkeypatch.setenv("HOROVOD_HLO_LINT_PAD_WASTE_MIN_PCT", "60")
    assert hlo.lint_text(fixture_text("hvd204_resnet_block")) == []


def test_hvd204_multi_dim_contraction_uses_extent():
    """A dot contracting over (16, 64) jointly is a 1024-extent — lane
    aligned — NOT two unaligned dims (the backward dL/dW shape)."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda a, b: jnp.einsum("bsd,bsf->df", a, b))
    t = f.lower(jnp.ones((16, 64, 256), jnp.float32),
                jnp.ones((16, 64, 512), jnp.float32)).as_text()
    assert [f for f in hlo.lint_text(t) if f.rule_id == "HVD204"] == []


def test_hvd205_message_names_consumer():
    fs = hlo.lint_text(fixture_text("hvd205_upcast_matmul"))
    assert "dot_general" in fs[0].message


# ------------------------------------------------------ lint surface

def test_lint_select_ignore():
    text = fixture_text("hvd204_resnet_block")
    assert rules_of(hlo.lint_text(text, select=["HVD201"])) == []
    assert rules_of(hlo.lint_text(text, ignore=["HVD204"])) == []


def test_lint_files_unreadable_is_hvd999(tmp_path):
    fs = hlo.lint_files([str(tmp_path / "missing.mlir")])
    assert fs[0].rule_id == "HVD999"


def test_lint_summary_shape():
    s = hlo.lint_summary(fixture_text("hvd204_resnet_block"), "fx")
    assert s["count"] >= 3 and not s["clean"]
    assert s["rules"] == {"HVD204": s["count"]}
    assert all("HVD204" in line for line in s["findings"])
    clean = hlo.lint_summary(fixture_text("hvd205_upcast_accum"), "fx")
    assert clean == {"count": 0, "clean": True}


def test_lint_summary_records_metrics():
    from horovod_tpu.observability import metrics as m
    before = _hlo_metric_total(m)
    hlo.lint_summary(fixture_text("hvd202_host_callback"), "fx")
    assert _hlo_metric_total(m) == before + 1


def _hlo_metric_total(m):
    total = 0.0
    for line in m.registry().render().splitlines():
        if line.startswith("hvdhlo_findings_total{"):
            total += float(line.rsplit(" ", 1)[1])
    return total


# -------------------------------------------------------------- CLI

def _fixture_path(name):
    return os.path.join(FIXDIR, f"{name}.mlir")


def test_cli_hlo_text_output(capsys):
    rc = run_cli(["--hlo", _fixture_path("hvd205_upcast_matmul")])
    out = capsys.readouterr().out
    assert rc == 1
    assert "HVD205" in out and ".mlir:" in out


def test_cli_hlo_json_and_baseline_roundtrip(tmp_path, capsys):
    fx = _fixture_path("hvd204_resnet_block")
    rc = run_cli(["--hlo", fx, "--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1 and doc["count"] >= 3
    base = tmp_path / "base.json"
    base.write_text(json.dumps(doc))
    assert run_cli(["--hlo", fx, "--baseline", str(base)]) == 0
    err = capsys.readouterr().out
    assert "clean" in err
    # a DIFFERENT module's findings still gate against that baseline
    assert run_cli(["--hlo", _fixture_path("hvd202_host_callback"),
                    "--baseline", str(base)]) == 1


def test_cli_hlo_unreadable_baseline_exit_2(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text("not json")
    assert run_cli(["--hlo", _fixture_path("hvd202_host_callback"),
                    "--baseline", str(bad)]) == 2


def test_cli_list_rules_includes_hvd2xx(capsys):
    assert run_cli(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in ("HVD201", "HVD202", "HVD203", "HVD204", "HVD205"):
        assert rid in out
    assert "HVD001" in out  # AST rules still listed


def test_cli_select_applies_in_hlo_mode(capsys):
    rc = run_cli(["--hlo", _fixture_path("hvd204_resnet_block"),
                  "--select", "HVD201"])
    capsys.readouterr()
    assert rc == 0


# ------------------------------------------- acceptance: --hlo-step lm

def test_hlo_step_lm_clean_under_default_config(monkeypatch, capsys):
    """The `make hlo-lint` gate: the canonical LM-shaped DP step under
    the default fusion config lowers clean against the checked-in
    (empty) baseline."""
    for var in ("HOROVOD_FUSION_THRESHOLD", "HOROVOD_BUCKET_CAP",
                "HOROVOD_HLO_LINT_MAX_COLLECTIVE_BYTES"):
        monkeypatch.delenv(var, raising=False)
    baseline = os.path.join(os.path.dirname(HERE), "scripts",
                            "hvdhlo_baseline.json")
    rc = run_cli(["--hlo-step", "lm", "--baseline", baseline])
    capsys.readouterr()
    assert rc == 0


def test_hlo_step_lm_giant_plan_trips_hvd201(monkeypatch):
    """ISSUE 8 acceptance: reintroducing the pre-PR-6 single-giant-
    allreduce plan (threshold=64MB, cap lifted) trips HVD201 on
    CPU-only CI."""
    monkeypatch.setenv("HOROVOD_FUSION_THRESHOLD", str(64 << 20))
    monkeypatch.setenv("HOROVOD_BUCKET_CAP", "0")
    text = hlo.lower_step_text("lm")
    findings = hlo.lint_text(text, path=hlo.step_path("lm"))
    assert any(f.rule_id == "HVD201" and "giant" in f.message
               for f in findings), [f.render() for f in findings]


def test_lower_step_unknown_program():
    with pytest.raises(ValueError):
        hlo.lower_step_text("nope")


# ---------------------------------- acceptance: --hlo-step resnet_block

def test_hlo_step_resnet_block_clean_when_padded(monkeypatch, capsys):
    """The `make conv-smoke` gate (ISSUE 12): the C=64 ResNet-block
    step — the live twin of the hvd204_resnet_block fixture — lowers
    CLEAN against the checked-in (empty) baseline once the layout pass
    (ops/layout.py) pads the declared stack to the lane width."""
    monkeypatch.delenv("HOROVOD_LAYOUT_PAD", raising=False)
    baseline = os.path.join(os.path.dirname(HERE), "scripts",
                            "hvdhlo_baseline.json")
    rc = run_cli(["--hlo-step", "resnet_block", "--baseline", baseline])
    capsys.readouterr()
    assert rc == 0


def test_hlo_step_resnet_block_unpadded_trips_hvd204(monkeypatch):
    """The regression canary both ways: reverting the layout pass
    (HOROVOD_LAYOUT_PAD=0) resurfaces the width-64 channel dims and
    HVD204 reports the 50% padding waste — exactly what the checked-in
    C=64 fixture pins statically, now pinned against the LIVE step
    program too."""
    monkeypatch.setenv("HOROVOD_LAYOUT_PAD", "0")
    text = hlo.lower_step_text("resnet_block")
    findings = hlo.lint_text(text, path=hlo.step_path("resnet_block"))
    hvd204 = [f for f in findings if f.rule_id == "HVD204"]
    assert hvd204, [f.render() for f in findings]
    assert any("= 64 " in f.message and "50.0%" in f.message
               for f in hvd204), [f.render() for f in hvd204]
