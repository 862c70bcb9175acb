"""The stretch parallelism paths (tp/sp/ring, pp/ep/MoE) compiled by
the REAL TPU compiler — not just the virtual CPU mesh the rest of the
suite (and the driver dryrun) uses.

AOT compile-only v5e:2x4 topology (see test_overlap_hlo.py): validates
that the shardings lower through the actual TPU backend — layout
assignment, collective lowering, pipelining — and that the expected
collective structure is present: ring attention produces
collective-permutes, MoE expert dispatch produces all-to-alls, the
pipeline loop a while op. Skips where the TPU compile-only client is
unavailable.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from horovod_tpu.models import transformer as tfm
from horovod_tpu.parallel.mesh import MeshSpec, build_mesh


def _v5e_devices():
    try:
        from jax.experimental import topologies
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x4")
    except Exception as e:  # pragma: no cover - CI without libtpu
        pytest.skip(f"TPU compile-only client unavailable: {e}")
    return list(topo.devices)


def _compile(spec, cfg, seq, batch):
    mesh = build_mesh(spec, devices=_v5e_devices()[:spec.total])
    tfm.validate_cfg_for_mesh(cfg, mesh)
    params = tfm.init(jax.random.PRNGKey(0), cfg)
    opt = optax.adam(1e-3)
    opt_state = opt.init(params)
    step = tfm.build_train_step(cfg, mesh, opt)
    tokens = jnp.zeros((batch, seq), jnp.int32)
    lower = step.lower if hasattr(step, "lower") else \
        jax.jit(step).lower
    return lower(params, opt_state, tokens, tokens).compile().as_text()


def test_ring_tp_sp_train_step_lowers_on_tpu():
    cfg = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=4,
                                d_ff=64, n_layers=2, max_seq=64,
                                attn="ring")
    txt = _compile(MeshSpec(dp=2, sp=2, tp=2), cfg, seq=32, batch=8)
    # ring attention rotates k/v around the sp axis
    assert txt.count("collective-permute") >= 4, \
        "ring attention lost its collective-permutes on TPU"
    # tp + dp gradient reduction
    assert "all-reduce" in txt


def test_pp_ep_moe_train_step_lowers_on_tpu():
    cfg = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=4,
                                d_ff=64, n_layers=2, max_seq=64,
                                attn="local", num_experts=4,
                                microbatches=2)
    txt = _compile(MeshSpec(dp=2, pp=2, ep=2), cfg, seq=32, batch=8)
    # MoE expert dispatch/return rides all-to-all over the ep axis
    assert "all-to-all" in txt, "MoE dispatch lost its all-to-alls"
    # pipeline microbatch loop
    assert "while(" in txt
    assert "all-reduce" in txt


@pytest.mark.parametrize("experts,kernels", [
    (dict(experts_per_token=2, mlp="swiglu", norm="rmsnorm", positions="rope",
          qk_norm=True, load_balance_coef=0.01, router_z_coef=0.001), 11),
    (dict(experts_per_token=1), 7)], ids=["olmoe-top2-swiglu", "top1-gelu"])
def test_olmoe_block_train_step_lowers_on_tpu(experts, kernels, monkeypatch):
    """An expert block on one rank (dropless routing), bf16 with remat: the
    grouped matmuls are the Mosaic kernels of ops/grouped_matmul.py, told as
    the benchmark's readers tell them (benchmark/harness/scopes.py): custom
    calls of seven operands, five of metadata and two matrices, and one
    result. The four parts of the expert layer keep their scopes in the
    compiled text. A gated expert has eleven a block: three forward, two
    under remat (gate and up, for the hidden rows the backward pass needs)
    and six backward. The down product is not among the replayed ones: the
    router weights multiply its input rows, so nothing after it is a
    residual (twelve until PR 29, when they multiplied its gathered result).
    An ungated top-1 expert has seven: two forward, one under remat, four
    backward. (Until PR 31 they were the kernels the compiler made of
    `lax.ragged_dot`, with a metadata kernel beside each.)"""
    from benchmark.harness import hlo, scopes
    from horovod_tpu.ops import _pallas
    monkeypatch.setattr(_pallas, "interpret", lambda: False)
    cfg = tfm.TransformerConfig(
        vocab=512, d_model=256, n_heads=2, d_ff=128, n_layers=2, max_seq=256,
        num_experts=8, attn="local", dtype=jnp.bfloat16, remat=True,
        **experts)
    txt = _compile(MeshSpec(), cfg, seq=256, batch=2)
    found = scopes.grouped_kernels(hlo.index(txt))
    assert list(found.values()) == [scopes.GROUPED_MATMUL] * kernels
    assert txt.count('custom_call_target="tpu_custom_call"') == kernels
    for part in ("route", "dispatch", "experts", "combine"):
        assert f"/moe.{part}/" in txt, part
    assert "all-to-all" not in txt and "all-reduce" not in txt


def _computations(txt):
    """name -> body text of every computation of a compiled program; the
    entry computation under "ENTRY"."""
    import re
    found = {}
    for block in re.split(r"\n(?=(?:ENTRY\s+)?%?[\w.\-]+\s*\([^\n]*->[^\n]*\{\n)",
                          txt):
        head = block.split("(", 1)[0].strip()
        found["ENTRY" if head.startswith("ENTRY") else head.lstrip("%")] = \
            block
    return found


DP_CFG = tfm.TransformerConfig(vocab=256, d_model=128, n_heads=4, d_ff=512,
                               n_layers=6, max_seq=64, attn="local",
                               dtype=jnp.bfloat16, remat=True)


def test_dp_gradients_are_reduced_inside_the_backward_loop_on_tpu():
    """MeshSpec(dp=4): the layers' gradient reduction sits in a while body
    (the backward loop) in asynchronous form, as collective-permute
    `-start`/`-done` pairs, and the entry computation all-reduces no
    stacked (n_layers, ...) leaf: what is left for after the loop are the
    all-gathers that complete the shards and the leaves outside the scan.
    That it compiles at all says the installed compiler knows the option
    `build_train_step` gives it."""
    import re
    comps = _computations(_compile(MeshSpec(dp=4), DP_CFG, seq=32, batch=8))
    bodies = re.findall(r"while\([^\n]*body=%?([\w.\-]+)", comps["ENTRY"])
    assert bodies, "the layer scans were not compiled to loops"
    in_loop = [b for b in bodies
               if "collective-permute-start" in comps[b]
               and "collective-permute-done" in comps[b]]
    assert in_loop, ("no backward loop issues the layers' gradient "
                     "reduction asynchronously")
    for b in in_loop:
        # issued and awaited inside the loop, and not by an all-reduce or a
        # reduce-scatter, which the core would wait for
        assert " all-reduce(" not in comps[b]
        assert " reduce-scatter(" not in comps[b]
    stacked = re.compile(r"= \(?bf16\[%d,\d+,\d+[^\n]* all-reduce\("
                         % DP_CFG.n_layers)
    assert not stacked.search(comps["ENTRY"]), \
        "a stacked layer gradient is all-reduced after the backward loop"
    assert "all-gather" in "".join(comps.values())
    assert "all-reduce" in comps["ENTRY"]      # loss, embed, unembed, norms


def test_single_device_train_step_holds_no_collective_on_tpu():
    txt = _compile(MeshSpec(), DP_CFG, seq=32, batch=8)
    for kind in ("all-reduce", "all-gather", "reduce-scatter",
                 "collective-permute", "all-to-all"):
        assert kind not in txt, f"{kind} in the one-device train step"


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_no_scatter_under_the_lookup_in_the_compiled_step(tied, monkeypatch):
    """The lookup's backward pass (ops/row_gather.py `lookup_rows`) in a bf16
    flash + remat step on one rank: no instruction under `vocab.embed` is a
    scatter, where plain indexing leaves one there (so the search would find
    it), and the step holds the Mosaic kernels it held with plain indexing,
    no more: the benchmark's flash readers sum every custom call of the LM
    cells' program. A tied head adds its gradient to the lookup's."""
    from horovod_tpu.ops import _pallas
    monkeypatch.setattr(_pallas, "interpret", lambda: False)
    cfg = tfm.TransformerConfig(
        vocab=512, d_model=256, n_heads=2, d_ff=512, n_layers=2, max_seq=256,
        attn="flash", dtype=jnp.bfloat16, remat=True, tied_head=tied)

    def scatters_and_kernels():
        txt = _compile(MeshSpec(), cfg, seq=256, batch=2)
        return ([line.strip()[:160] for line in txt.splitlines()
                 if " scatter(" in line and "vocab.embed" in line],
                txt.count('custom_call_target="tpu_custom_call"'))

    scatters, kernels = scatters_and_kernels()
    monkeypatch.setattr(tfm, "lookup_rows",
                        lambda table, ids: (table[ids], table))
    indexed, kernels_indexed = scatters_and_kernels()
    assert indexed and not scatters, scatters
    # three a flash layer, one loop body for the two layers: the forward,
    # its remat repeat and the one backward kernel (dq and dk/dv: four,
    # until PR 55)
    assert kernels == kernels_indexed == 3
