"""hvdshard suite (ISSUE 13 tentpole): static sharding & per-device
memory analysis of lowered XLA programs.

The golden fixtures under ``tests/fixtures/hlo/`` are tiny sharded
programs lowered on the 8-device virtual CPU mesh (``.mlir`` =
pre-partition StableHLO, ``.hlo`` = post-SPMD compiled text;
regenerate with ``scripts/gen_hlo_fixtures.py``), so the per-rule
tests are hermetic. The acceptance tests DO lower live: the canonical
``--hlo-step lm_sharded`` 2-D (batch x model) mesh program must lint
clean under the default sharded config and must trip HVD301+HVD302
when every parameter is forced fully replicated — the GSPMD
"forgot to annotate the params" failure, on CPU-only CI.
"""

import json
import os

import pytest

from horovod_tpu.analysis import hlo, shard, shard_rules
from horovod_tpu.analysis.driver import run_cli

HERE = os.path.dirname(__file__)
FIXDIR = os.path.join(HERE, "fixtures", "hlo")

_MB = 1024 * 1024


def fixture_text(name):
    for ext in ("mlir", "hlo"):
        p = os.path.join(FIXDIR, f"{name}.{ext}")
        if os.path.exists(p):
            with open(p, encoding="utf-8") as f:
                return f.read()
    raise FileNotFoundError(name)


def fixture_path(name):
    for ext in ("mlir", "hlo"):
        p = os.path.join(FIXDIR, f"{name}.{ext}")
        if os.path.exists(p):
            return p
    raise FileNotFoundError(name)


def rules_of(findings):
    return sorted({f.rule_id for f in findings})


# ------------------------------------------------ sharding-string parser

def test_parse_sharding_replicated_maximal_manual():
    assert shard.parse_sharding("{replicated}").kind == "replicated"
    assert shard.parse_sharding("{replicated}").fully_replicated
    assert shard.parse_sharding("{maximal device=0}").kind == "maximal"
    assert shard.parse_sharding("{manual}").kind == "manual"
    assert shard.parse_sharding(None) is None
    assert shard.parse_sharding("{garbage}") is None


def test_parse_sharding_v1_device_list():
    s = shard.parse_sharding("{devices=[2,2]0,1,2,3}")
    assert s.kind == "tiled"
    assert s.tile_dims == (2, 2)
    assert s.replicate_factor == 1
    assert s.shard_factor == 4
    assert s.assignment == (0, 1, 2, 3)
    # device -> shard index is the identity here
    assert s.shard_of(4) == (0, 1, 2, 3)


def test_parse_sharding_v2_iota():
    s = shard.parse_sharding("{devices=[2,1,4]<=[8] "
                             "last_tile_dim_replicate}")
    assert s.tile_dims == (2, 1)
    assert s.replicate_factor == 4
    assert s.shard_factor == 2
    # devices 0-3 hold shard 0, devices 4-7 hold shard 1
    assert s.shard_of(8) == (0, 0, 0, 0, 1, 1, 1, 1)


def test_parse_sharding_v2_transpose():
    """The [2,4] mesh's model-axis sharding prints with an iota
    transpose: devices= [4,1,2]<=[2,4]T(1,0) — sharded 4-way over the
    INNER mesh axis, replicated over the outer 2."""
    s = shard.parse_sharding(
        "{devices=[4,1,2]<=[2,4]T(1,0) last_tile_dim_replicate}")
    assert s.shard_factor == 4 and s.replicate_factor == 2
    # mesh (2,4): device b*4+m holds shard m
    assert s.shard_of(8) == (0, 1, 2, 3, 0, 1, 2, 3)


def test_parse_sharding_full_mesh():
    s = shard.parse_sharding("{devices=[2,1,4]<=[8]}")
    assert s.shard_factor == 8 and s.replicate_factor == 1
    assert s.shard_of(8) == tuple(range(8))


def test_parse_sharding_foreign_device_count():
    """An annotation for a different device count must refuse to map,
    not mis-attribute shards."""
    s = shard.parse_sharding("{devices=[2,1,4]<=[8]}")
    assert s.shard_of(4) is None


def test_per_device_bytes_stablehlo_divides():
    t = hlo.TensorType("f32", (8192, 256))
    spec = shard.parse_sharding(
        "{devices=[4,1,2]<=[2,4]T(1,0) last_tile_dim_replicate}")
    assert shard.per_device_bytes(t, spec, "stablehlo") == 8 * _MB / 4
    assert shard.per_device_bytes(t, None, "stablehlo") == 8 * _MB
    # post-SPMD shapes are already per-device: bytes pass through
    assert shard.per_device_bytes(t, spec, "hlo") == 8 * _MB


def test_per_device_bytes_uneven_tiling_rounds_up():
    t = hlo.TensorType("f32", (10, 4))
    spec = shard.parse_sharding("{devices=[4,1]0,1,2,3}")
    # ceil(10/4)=3 rows per device
    assert shard.per_device_bytes(t, spec, "stablehlo") == 3 * 4 * 4


def test_bytes_env_suffixes(monkeypatch):
    monkeypatch.setenv("X_BYTES", "16G")
    assert shard._bytes_env("X_BYTES", None) == 16 * (1 << 30)
    monkeypatch.setenv("X_BYTES", "1.5M")
    assert shard._bytes_env("X_BYTES", None) == int(1.5 * _MB)
    monkeypatch.setenv("X_BYTES", "4096")
    assert shard._bytes_env("X_BYTES", None) == 4096
    monkeypatch.delenv("X_BYTES")
    assert shard._bytes_env("X_BYTES", None) is None


def test_bytes_env_garbage_raises_loud(monkeypatch):
    """A malformed budget must NOT silently disarm the gate it was set
    to arm (the flops.py loud-on-garbage policy): 16GiB, 1T, underscores
    all raise with the knob named."""
    for bad in ("16GiB", "1T", "16_000", "garbage"):
        monkeypatch.setenv("HOROVOD_HLO_LINT_HBM_BUDGET", bad)
        with pytest.raises(ValueError, match="HOROVOD_HLO_LINT_HBM"):
            shard_rules.hbm_budget_bytes()


# ------------------------------------------- parser satellite (hlo.py)

def test_hlo_param_sharding_recorded_stablehlo():
    prog = hlo.parse(fixture_text("hvd301_replicated_emb"), "fx")
    assert prog.num_partitions == 8
    assert prog.entry_params[0].sharding == "{replicated}"
    assert "devices=" in prog.entry_params[1].sharding


def test_hlo_param_sharding_recorded_hlo_text():
    prog = hlo.parse(fixture_text("hvd302_allgather_inserted"), "fx")
    assert prog.fmt == "hlo" and prog.num_partitions == 8
    ann = [p for p in prog.entry_params if p.sharding]
    assert ann, "compiled entry params lost their sharding attrs"
    assert any("devices=" in p.sharding for p in ann)


def test_hlo_call_boundary_params_carry_sharding():
    """Sharding attrs on a non-entry func's args (a `call`ed shard_map
    body / sub-function boundary) are recorded uniformly with the
    entry signature — the PR's parser satellite, both textual forms."""
    text = ('module @m attributes {mhlo.num_partitions = 4 : i32} {\n'
            '  func.func public @main(%arg0: tensor<64xf32> '
            '{mhlo.sharding = "{devices=[4]<=[4]}"}) -> tensor<64xf32> {\n'
            '    %0 = call @body(%arg0) : (tensor<64xf32>) -> tensor<64xf32>\n'
            '    return %0 : tensor<64xf32>\n'
            '  }\n'
            '  func.func private @body(%arg0: tensor<64xf32> '
            '{jax.buffer_donor = true, mhlo.sharding = "{replicated}"}) '
            '-> tensor<64xf32> {\n'
            '    %0 = stablehlo.add %arg0, %arg0 : tensor<64xf32>\n'
            '    return %0 : tensor<64xf32>\n'
            '  }\n'
            '}')
    prog = hlo.parse(text, "t")
    body = [p for p in prog.params if p.scope == "body"]
    assert body and body[0].sharding == "{replicated}"
    assert body[0].donated
    assert prog.entry_params[0].sharding == "{devices=[4]<=[4]}"


def test_hlo_text_non_entry_params_carry_sharding():
    text = ("HloModule m, num_partitions=4\n"
            "\n"
            "%helper (p.0: f32[64]) -> f32[64] {\n"
            "  %p.0 = f32[64]{0} parameter(0), sharding={replicated}\n"
            "  ROOT %a = f32[64]{0} add(f32[64]{0} %p.0, f32[64]{0} %p.0)\n"
            "}\n"
            "\n"
            "ENTRY %main (p: f32[64]) -> f32[64] {\n"
            "  %p = f32[64]{0} parameter(0), "
            "sharding={devices=[4]<=[4]}\n"
            "  ROOT %c = f32[64]{0} call(f32[64]{0} %p), "
            "to_apply=%helper\n"
            "}\n")
    prog = hlo.parse(text, "t")
    assert prog.num_partitions == 4
    helper = [p for p in prog.params if p.scope == "%helper"]
    assert helper and helper[0].sharding == "{replicated}"
    assert prog.entry_params[0].sharding == "{devices=[4]<=[4]}"


def test_op_sharding_custom_call_constraint():
    prog = hlo.parse(fixture_text("hvd304_unused_axis"), "fx")
    wsc = [op for op in prog.ops
           if op.opcode == "custom_call" and hlo.op_sharding(op)]
    assert wsc, "with_sharding_constraint annotation not recorded"
    assert "devices=" in hlo.op_sharding(wsc[0])


def test_donation_bit_survives_nested_sharding_attr():
    """Two-level attr nesting: a donor bit riding next to a sharding
    string that itself contains a brace list."""
    text = ('module @m {\n'
            '  func.func public @main(%arg0: tensor<2097152xf32> '
            '{jax.buffer_donor = true, mhlo.sharding = '
            '"{devices=[2,2]<=[4] last_tile_dims={replicated}}"}) '
            '-> tensor<2097152xf32> {\n'
            '    return %arg0 : tensor<2097152xf32>\n'
            '  }\n'
            '}')
    prog = hlo.parse(text, "t")
    assert prog.entry_params[0].donated
    spec = shard.parse_sharding(prog.entry_params[0].sharding)
    assert spec.tile_dims == (2,) and spec.replicate_factor == 2


# ---------------------------------------------- partition refinement

def _ann(spec_text, nbytes=2 * _MB):
    return shard.AnnotatedTensor(
        "t", hlo.TensorType("f32", (nbytes // 4,)),
        shard.parse_sharding(spec_text), 1, "param")


def test_partition_classes_complete_coverage():
    """One tensor sharded over each axis: every device distinguished."""
    ts = [_ann("{devices=[2,1,4]<=[8] last_tile_dim_replicate}"),
          _ann("{devices=[4,1,2]<=[2,4]T(1,0) last_tile_dim_replicate}")]
    assert shard.partition_classes(ts, 8) == 8


def test_partition_classes_unused_axis():
    """Everything sharded over the batch axis only: the 4-wide model
    axis collapses to 2 classes."""
    ts = [_ann("{devices=[2,1,4]<=[8] last_tile_dim_replicate}"),
          _ann("{replicated}")]
    assert shard.partition_classes(ts, 8) == 2


def test_partition_classes_unmappable_returns_none():
    ts = [_ann("{devices=[2,1,4]<=[8] last_tile_dim_replicate}"),
          shard.AnnotatedTensor("x", hlo.TensorType("f32", (4,)),
                                None, 1, "param")]
    assert shard.partition_classes(ts, 8) is None


# ------------------------------------------------- peak-memory model

def _mini_hlo(donated):
    alias = (", input_output_alias={ {}: (0, {}, may-alias) }"
             if donated else "")
    return (f"HloModule m, is_scheduled=true{alias}\n"
            "\n"
            "ENTRY %main (p: f32[1048576]) -> f32[1048576] {\n"
            "  %p = f32[1048576]{0} parameter(0)\n"
            "  %a = f32[1048576]{0} add(f32[1048576]{0} %p, "
            "f32[1048576]{0} %p)\n"
            "  ROOT %b = f32[1048576]{0} multiply(f32[1048576]{0} %a, "
            "f32[1048576]{0} %a)\n"
            "}\n")


def test_peak_memory_donation_aware():
    """4 MB input, two 4 MB ops. Undonated: p lives to the end next to
    a and b -> 12 MB peak. Donated: p dies after its last use (the
    add) -> 8 MB peak. The donation bit is worth exactly one buffer."""
    est = shard.peak_memory(hlo.parse(_mini_hlo(donated=False), "t"))
    assert est.peak_bytes == 12 * _MB
    assert est.args_bytes == 4 * _MB and est.donated_bytes == 0
    est = shard.peak_memory(hlo.parse(_mini_hlo(donated=True), "t"))
    assert est.peak_bytes == 8 * _MB
    assert est.donated_bytes == 4 * _MB


def test_peak_memory_alias_ops_do_not_allocate():
    text = ("HloModule m, is_scheduled=true\n"
            "\n"
            "ENTRY %main (p: f32[1048576]) -> f32[1048576] {\n"
            "  %p = f32[1048576]{0} parameter(0)\n"
            "  %bc = f32[1048576]{0} bitcast(f32[1048576]{0} %p)\n"
            "  ROOT %a = f32[1048576]{0} add(f32[1048576]{0} %bc, "
            "f32[1048576]{0} %bc)\n"
            "}\n")
    est = shard.peak_memory(hlo.parse(text, "t"))
    assert est.peak_bytes == 8 * _MB  # p + a; the bitcast is free


def test_peak_memory_alias_last_use_keeps_buffer_alive():
    """An alias's last use must not free the underlying buffer while
    the ORIGINAL name is still consumed later: liveness is keyed on
    canonical buffers, not SSA names."""
    text = ("HloModule m, is_scheduled=true\n"
            "\n"
            "ENTRY %main (p: f32[1048576]) -> f32[1048576] {\n"
            "  %p = f32[1048576]{0} parameter(0)\n"
            "  %bc = f32[1048576]{0} bitcast(f32[1048576]{0} %p)\n"
            "  %a = f32[1048576]{0} add(f32[1048576]{0} %bc, "
            "f32[1048576]{0} %bc)\n"
            "  ROOT %b = f32[1048576]{0} multiply(f32[1048576]{0} %a, "
            "f32[1048576]{0} %p)\n"
            "}\n")
    est = shard.peak_memory(hlo.parse(text, "t"))
    # p must still be live during b: p + a + b = 12 MB
    assert est.peak_bytes == 12 * _MB


def test_peak_memory_tuple_keeps_all_elements_alive():
    """A tuple aliases ALL its operands: element 1 must stay live past
    the tuple op while a later get-tuple-element still reads it (the
    tuple op must not count as its last use), and the gte must resolve
    to the ELEMENT buffer, not allocate."""
    text = ("HloModule m, is_scheduled=true\n"
            "\n"
            "ENTRY %main (p: f32[1048576]) -> f32[1048576] {\n"
            "  %p = f32[1048576]{0} parameter(0)\n"
            "  %a = f32[1048576]{0} add(f32[1048576]{0} %p, "
            "f32[1048576]{0} %p)\n"
            "  %t = (f32[1048576]{0}, f32[1048576]{0}) "
            "tuple(f32[1048576]{0} %p, f32[1048576]{0} %a)\n"
            "  %big = f32[2097152]{0} iota(), iota_dimension=0\n"
            "  %gte = f32[1048576]{0} get-tuple-element((f32[1048576]{0},"
            " f32[1048576]{0}) %t), index=1\n"
            "  ROOT %b = f32[1048576]{0} multiply(f32[1048576]{0} %gte, "
            "f32[1048576]{0} %gte)\n"
            "}\n")
    est = shard.peak_memory(hlo.parse(text, "t"))
    # during %big: p(4, undonated) + a(4, live via the tuple) + big(8)
    # = 16 MB; the gte aliases %a (no new buffer), then b adds 4 with
    # big freed -> the 16 MB point is the peak
    assert est.peak_bytes == 16 * _MB


def test_peak_memory_callee_interior_counts():
    """A call's interior temps ride on top of the caller's live set;
    its params and root alias the caller's buffers (not re-counted)."""
    text = ("HloModule m, is_scheduled=true\n"
            "\n"
            "%helper (hp: f32[1048576]) -> f32[1048576] {\n"
            "  %hp = f32[1048576]{0} parameter(0)\n"
            "  %t = f32[1048576]{0} add(f32[1048576]{0} %hp, "
            "f32[1048576]{0} %hp)\n"
            "  ROOT %r = f32[1048576]{0} multiply(f32[1048576]{0} %t, "
            "f32[1048576]{0} %t)\n"
            "}\n"
            "\n"
            "ENTRY %main (p: f32[1048576]) -> f32[1048576] {\n"
            "  %p = f32[1048576]{0} parameter(0)\n"
            "  ROOT %c = f32[1048576]{0} call(f32[1048576]{0} %p), "
            "to_apply=%helper\n"
            "}\n")
    est = shard.peak_memory(hlo.parse(text, "t"))
    # caller: p (4) + c (4); interior: t (4, root r aliases c)
    assert est.peak_bytes == 12 * _MB


def test_peak_memory_stablehlo_returns_none():
    assert shard.peak_memory(
        hlo.parse(fixture_text("hvd301_sharded_emb"), "t")) is None


def test_peak_memory_real_compiled_module_vs_xla():
    """The estimate on a real compiled module must land within 1.5x of
    XLA's own buffer-assignment numbers."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x, w: jnp.tanh(x @ w) @ w.T)
    x = jnp.ones((512, 512), jnp.float32)
    comp = f.lower(x, x).compile()
    est = shard.estimate_compiled_text(comp.as_text())
    assert est is not None and est.peak_bytes > 0
    ma = comp.memory_analysis()
    xla_peak = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    assert xla_peak > 0
    ratio = est.peak_bytes / xla_peak
    assert 1 / 1.5 <= ratio <= 1.5, (est.as_dict(), xla_peak)


def test_memory_estimate_as_dict_shape():
    est = shard.peak_memory(hlo.parse(_mini_hlo(donated=True), "t"))
    d = est.as_dict()
    assert d["peak_mb"] == 8.0
    assert d["top_live"] and "buffer" in d["top_live"][0]


# ------------------------------------------------- rule fixtures

#: fixture name -> rule set the analyzer must produce (the golden
#: contract: each positive flags exactly its rule; twins are clean).
#: HVD303 gates only under an explicit budget — tested separately.
FIXTURE_RULES = {
    "hvd301_replicated_emb": ["HVD301"],
    "hvd301_sharded_emb": [],
    "hvd302_allgather_inserted": ["HVD302"],
    "hvd302_reshard_free": [],
    "hvd303_overbudget": [],
    "hvd303_donated_underbudget": [],
    "hvd304_unused_axis": ["HVD304"],
    "hvd304_used_axes": [],
    "hvd305_allreduce_slice": ["HVD305"],
    "hvd305_psum_scatter": [],
}


@pytest.mark.parametrize("name,expected", sorted(FIXTURE_RULES.items()))
def test_fixture_rules(name, expected):
    findings = shard.lint_text(fixture_text(name), path=name)
    assert rules_of(findings) == expected, \
        [f.render() for f in findings]


def test_hvd301_message_names_size_and_partitions():
    fs = shard.lint_text(fixture_text("hvd301_replicated_emb"))
    assert "8.0 MB" in fs[0].message
    assert "8-partition" in fs[0].message


def test_hvd301_threshold_floor(monkeypatch):
    monkeypatch.setenv("HOROVOD_SHARD_LINT_MIN_REPLICATED_BYTES", "16M")
    assert shard.lint_text(fixture_text("hvd301_replicated_emb")) == []


def test_hvd302_message_names_origin_and_bytes(monkeypatch):
    fs = shard.lint_text(fixture_text("hvd302_allgather_inserted"))
    assert "all_gather" in fs[0].message
    assert "MB" in fs[0].message
    monkeypatch.setenv("HOROVOD_SHARD_LINT_MIN_RESHARD_BYTES", "1G")
    assert shard.lint_text(
        fixture_text("hvd302_allgather_inserted")) == []


def test_hvd302_user_collective_exempt():
    """A user-requested all_gather (shard_map lax.all_gather: metadata
    traces to the collective primitive) must NOT be flagged."""
    op = hlo.HloOp(
        1, "%ag", "all_gather", ("%p",),
        (hlo.TensorType("f32", (256, 512)),),
        (hlo.TensorType("f32", (2048, 512)),),
        'channel_id=1, metadata={op_name="jit(f)/jit(main)/'
        'all_gather[axis=0]"}', "main")
    assert shard.traceable_to_user_collective(op)
    inserted = hlo.HloOp(
        1, "%ag", "all_gather", ("%p",),
        (hlo.TensorType("f32", (256, 512)),),
        (hlo.TensorType("f32", (2048, 512)),),
        'channel_id=1, metadata={op_name="jit(f)/jit(main)/'
        'dot_general"}', "main")
    assert not shard.traceable_to_user_collective(inserted)
    no_meta = hlo.HloOp(1, "%ag", "all_gather", ("%p",), (), (),
                        "channel_id=1", "main")
    assert not shard.traceable_to_user_collective(no_meta)


def test_hvd303_budget_gates_fixture_pair(monkeypatch):
    """The over-budget vs donated-under-budget twins: static peaks are
    64 MB vs 48 MB; a 56M budget separates them — donation alone moves
    the program across the compile-time OOM gate."""
    monkeypatch.setenv("HOROVOD_HLO_LINT_HBM_BUDGET", "56M")
    over = shard.lint_text(fixture_text("hvd303_overbudget"))
    assert rules_of(over) == ["HVD303"], [f.render() for f in over]
    assert "56.0 MB budget" in over[0].message
    assert shard.lint_text(
        fixture_text("hvd303_donated_underbudget")) == []


def test_hvd303_silent_without_budget(monkeypatch):
    monkeypatch.delenv("HOROVOD_HLO_LINT_HBM_BUDGET", raising=False)
    assert shard.lint_text(fixture_text("hvd303_overbudget")) == []


def test_hvd304_message_names_waste():
    fs = shard.lint_text(fixture_text("hvd304_unused_axis"))
    assert "8 partitions" in fs[0].message
    assert "2 device group(s)" in fs[0].message


def test_hvd304_threshold(monkeypatch):
    monkeypatch.setenv("HOROVOD_SHARD_LINT_MIN_SHARDED_BYTES", "1G")
    assert shard.lint_text(fixture_text("hvd304_unused_axis")) == []


def test_hvd305_message_suggests_psum_scatter():
    fs = shard.lint_text(fixture_text("hvd305_allreduce_slice"))
    assert "psum_scatter" in fs[0].message


def test_hvd2xx_rules_ignore_shard_fixtures():
    """The HVD2xx family must not double-report on the sharding
    fixtures (family separation: hlo.lint_text stays HVD2xx-only)."""
    fs = hlo.lint_text(fixture_text("hvd301_replicated_emb"))
    assert not [f for f in fs if f.rule_id.startswith("HVD3")]


def test_lint_select_ignore():
    text = fixture_text("hvd301_replicated_emb")
    assert rules_of(shard.lint_text(text, select=["HVD302"])) == []
    assert rules_of(shard.lint_text(text, ignore=["HVD301"])) == []


def test_lint_files_unreadable_is_hvd999(tmp_path):
    fs = shard.lint_files([str(tmp_path / "missing.hlo")])
    assert fs[0].rule_id == "HVD999"


def test_lint_records_metrics():
    from horovod_tpu.observability import metrics as m

    def total():
        t = 0.0
        for line in m.registry().render().splitlines():
            if line.startswith("hvdshard_findings_total{"):
                t += float(line.rsplit(" ", 1)[1])
        return t

    before = total()
    shard.record_metrics(
        shard.lint_text(fixture_text("hvd301_replicated_emb")))
    assert total() == before + 1


# -------------------------------------------------------------- CLI

def test_cli_shard_text_output(capsys):
    rc = run_cli(["--shard", fixture_path("hvd301_replicated_emb")])
    out = capsys.readouterr().out
    assert rc == 1
    assert "HVD301" in out


def test_cli_shard_json_and_baseline_roundtrip(tmp_path, capsys):
    fx = fixture_path("hvd302_allgather_inserted")
    rc = run_cli(["--shard", fx, "--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1 and doc["count"] == 1
    base = tmp_path / "base.json"
    base.write_text(json.dumps(doc))
    assert run_cli(["--shard", fx, "--baseline", str(base)]) == 0
    out = capsys.readouterr().out + capsys.readouterr().err
    # a different module's findings still gate against that baseline
    assert run_cli(["--shard", fixture_path("hvd301_replicated_emb"),
                    "--baseline", str(base)]) == 1


def test_cli_shard_unreadable_baseline_exit_2(tmp_path, capsys):
    bad = tmp_path / "bad.json"
    bad.write_text("not json")
    assert run_cli(["--shard", fixture_path("hvd301_replicated_emb"),
                    "--baseline", str(bad)]) == 2
    capsys.readouterr()


def test_cli_shard_plus_hlo_runs_both_families(capsys):
    """--hlo --shard over one dump runs HVD2xx AND HVD3xx."""
    rc = run_cli(["--hlo", "--shard",
                  fixture_path("hvd301_replicated_emb"),
                  "--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    rules = {f["rule"] for f in doc["findings"]}
    assert "HVD301" in rules
    assert rc == 1


def test_cli_list_rules_includes_hvd3xx(capsys):
    assert run_cli(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in ("HVD301", "HVD302", "HVD303", "HVD304", "HVD305"):
        assert rid in out
    assert "HVD201" in out and "HVD001" in out  # other families listed


def test_cli_malformed_budget_knob_exit_2(monkeypatch, capsys):
    """A typo'd budget knob is a TOOL error on the driver convention
    (one-line diagnostic + exit 2), not findings (exit 1) and not a
    traceback — and never a silently disarmed gate."""
    monkeypatch.setenv("HOROVOD_HLO_LINT_HBM_BUDGET", "16GiB")
    rc = run_cli(["--shard", fixture_path("hvd303_overbudget")])
    err = capsys.readouterr().err
    assert rc == 2
    assert "16GiB" in err and "byte count" in err


def test_cli_shard_clean_fixture_exit_0(capsys):
    assert run_cli(["--shard",
                    fixture_path("hvd301_sharded_emb")]) == 0
    assert "clean" in capsys.readouterr().out


# ----------------------------------- acceptance: --hlo-step lm_sharded

def _clear_shard_env(monkeypatch):
    for var in ("HOROVOD_SHARD_LINT_REPLICATED",
                "HOROVOD_SHARD_LINT_MIN_REPLICATED_BYTES",
                "HOROVOD_SHARD_LINT_MIN_RESHARD_BYTES",
                "HOROVOD_SHARD_LINT_MIN_SHARDED_BYTES",
                "HOROVOD_HLO_LINT_HBM_BUDGET"):
        monkeypatch.delenv(var, raising=False)


def test_hlo_step_lm_sharded_clean_under_default_config(monkeypatch,
                                                        capsys):
    """The `make shard-lint` gate: the canonical 2-D (batch x model)
    mesh LM step — the first real consumer of parallel/mesh.py — lints
    clean against the checked-in (empty) baseline, pre- AND post-SPMD,
    under a 1 GiB per-device HBM budget."""
    _clear_shard_env(monkeypatch)
    monkeypatch.setenv("HOROVOD_HLO_LINT_HBM_BUDGET", "1G")
    baseline = os.path.join(os.path.dirname(HERE), "scripts",
                            "hvdshard_baseline.json")
    rc = run_cli(["--hlo-step", "lm_sharded", "--baseline", baseline])
    capsys.readouterr()
    assert rc == 0


def test_hlo_step_lm_sharded_replicated_twin_trips(monkeypatch):
    """ISSUE 13 acceptance: the forced fully-replicated-params lowering
    (HOROVOD_SHARD_LINT_REPLICATED=1) trips HVD301 on the 16 MB
    embedding AND HVD302 on the partitioner-inserted all-gather, on
    CPU-only CI."""
    _clear_shard_env(monkeypatch)
    monkeypatch.setenv("HOROVOD_SHARD_LINT_REPLICATED", "1")
    texts = shard.lower_sharded_step_texts()
    findings = (shard.lint_text(texts["stablehlo"], "<s>")
                + shard.lint_text(texts["hlo"], "<spmd>"))
    rules = {f.rule_id for f in findings}
    assert "HVD301" in rules and "HVD302" in rules, \
        [f.render() for f in findings]
    assert any(f.rule_id == "HVD301" and "16.0 MB" in f.message
               for f in findings)


def test_hlo_step_lm_runtime_clean_via_cli(monkeypatch, capsys):
    """ISSUE 14 satellite: the RUNTIME hybrid step — the actual
    DistributedOptimizer.sharded_step program, not just its GSPMD
    analysis twin — goes through the same CLI gate and lints clean
    against the same empty baseline (`make shard-lint` /
    `make gspmd-smoke`)."""
    _clear_shard_env(monkeypatch)
    monkeypatch.setenv("HOROVOD_HLO_LINT_HBM_BUDGET", "1G")
    baseline = os.path.join(os.path.dirname(HERE), "scripts",
                            "hvdshard_baseline.json")
    rc = run_cli(["--hlo-step", "lm_runtime", "--baseline", baseline])
    capsys.readouterr()
    assert rc == 0


def test_hlo_step_lm_runtime_replicated_twin_trips_via_cli(monkeypatch,
                                                           capsys):
    """HOROVOD_SHARD_LINT_REPLICATED=1 applies to the runtime gate too:
    the stored-and-stepped-replicated twin exits 1 with HVD301 on the
    16 MB embedding (the GSPMD twin keeps pinning HVD302's
    partitioner-inserted all-gather above)."""
    _clear_shard_env(monkeypatch)
    monkeypatch.setenv("HOROVOD_SHARD_LINT_REPLICATED", "1")
    rc = run_cli(["--hlo-step", "lm_runtime"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "HVD301" in out and "lm_runtime" in out


def test_lm_sharded_static_peak_within_budget_band(monkeypatch):
    """The canonical program's static per-device peak is ~25 MB: small
    enough that the 1 GiB CI budget gives a 40x regression margin,
    large enough that the estimate is clearly measuring something."""
    _clear_shard_env(monkeypatch)
    texts = shard.lower_sharded_step_texts(replicated=False)
    est = shard.estimate_compiled_text(texts["hlo"])
    assert est is not None
    assert 8 * _MB < est.peak_bytes < 256 * _MB, est.as_dict()
    assert est.num_partitions == 8


def test_lm_sharded_uses_parallel_mesh(monkeypatch):
    """The lowering really goes through parallel/mesh.py (the module's
    first consumer): a broken MeshSpec must surface, not be silently
    bypassed."""
    import horovod_tpu.parallel.mesh as mesh_mod

    def boom(*a, **k):
        raise RuntimeError("mesh_used")

    monkeypatch.setattr(mesh_mod, "build_mesh", boom)
    with pytest.raises(RuntimeError, match="mesh_used"):
        shard.lower_sharded_step_texts(replicated=False)


# ---------------------------------------------- parallel/mesh hardening

def test_mesh_spec_rejects_non_positive_axis():
    from horovod_tpu.common.exceptions import HorovodTpuError
    from horovod_tpu.parallel.mesh import MeshSpec

    with pytest.raises(HorovodTpuError, match="tp=0"):
        MeshSpec(tp=0)
    with pytest.raises(HorovodTpuError, match="dp=-2"):
        MeshSpec(dp=-2)


def test_mesh_spec_infer_validation():
    from horovod_tpu.common.exceptions import HorovodTpuError
    from horovod_tpu.parallel.mesh import MeshSpec

    s = MeshSpec.infer(8, tp=4)
    assert s.dp == 2 and s.tp == 4 and s.total == 8
    with pytest.raises(HorovodTpuError):
        MeshSpec.infer(8, tp=3)
    with pytest.raises(HorovodTpuError):
        MeshSpec.infer(0)


def test_build_mesh_2d_axes_and_duplicates():
    import jax
    from horovod_tpu.common.exceptions import HorovodTpuError
    from horovod_tpu.parallel.mesh import (
        MeshSpec, build_mesh, mesh_axis_sizes)

    mesh = build_mesh(MeshSpec.infer(8, tp=4))
    sizes = mesh_axis_sizes(mesh)
    assert sizes["dp"] == 2 and sizes["tp"] == 4
    devs = list(jax.devices())
    devs[1] = devs[0]
    with pytest.raises(HorovodTpuError, match="duplicate"):
        build_mesh(MeshSpec.infer(8, tp=4), devs)
