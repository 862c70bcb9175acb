"""The grouped matmul, the gated delta rule, the convolution in front of it
and the scans compiled by the chip's own compiler, without a chip: what the
interpreter cannot show, that Mosaic accepts each kernel for a described v5e
at the shapes the main path uses and that its custom calls keep the operands
and results the benchmark's readers know them by. (The flash kernels and
`olmohybrid-1chip`'s whole step: `tests/test_flash_tpu_aot.py`.)
The expert layer's grouped matmuls (ops/grouped_matmul.py): the
three products at the two expert cells' shapes, each custom call with the
operands and the result `benchmark/harness/scopes.py` tells a grouped
matmul by. The sum of rows that count (ops/row_gather.py) at the three
expert cells' shapes, and a one-rank expert layer's forward and backward
pass: its row kernels carry `moe.dispatch` or `moe.combine` and none of the
(operands, results) a reader of the benchmark tells another kernel by. The
model's own two scanned expert layers: the compiled step slices no expert
leaf out of its stack for the grouped matmuls (PR 44).
About two seconds each (the grouped kernels five); skipped only where the
topology cannot be described (tests/tpu_probe.py).
"""

import jax
import jax.numpy as jnp
import pytest

from horovod_tpu.ops.grouped_matmul import grouped_matmul
from test_flash_tpu_aot import HYBRID_CELL, PHI4_FLASH


#: (rows, D, F, experts) of the expert cells: all 64 experts and every
#: (token, expert) pair; the held eighth and its row buffer
OLMOE_EXPERTS = (65536, 2048, 1024, 64)      # olmoe-1chip
DSV2LITE_EXPERTS = (12288, 2048, 1408, 8)    # dsv2lite-1chip

#: (operands, results) of a grouped matmul's custom call: the five int32
#: arrays of `grouped_matmul.visits`, the two matrices; one array. The
#: contract with `benchmark/harness/scopes.py` `GROUPED_MATMUL`, whose
#: readers sum the kernels' time and count their executions by it.
GROUPED_SIGNATURE = (7, 1)


def _grouped_fwd(rows, weights, sizes):
    return grouped_matmul(rows, weights, sizes)


def _grouped_bwd(rows, weights, sizes):
    return jax.grad(
        lambda r, w: grouped_matmul(r, w, sizes).astype(jnp.float32).sum(),
        argnums=(0, 1))(rows, weights)


@pytest.mark.parametrize("product", ["up", "down"])
@pytest.mark.parametrize("shape", [OLMOE_EXPERTS, DSV2LITE_EXPERTS],
                         ids=["olmoe-1chip", "dsv2lite-1chip"])
def test_grouped_matmul_compiles_for_v5e(monkeypatch, shape, product):
    """The forward kernel, and the two of the backward pass (towards the
    rows, with the weights read transposed; towards the weights), of the up
    and gate products (D -> F) and of the down product (F -> D). DeepSeek's
    F = 1,408 is eleven lane tiles and is taken whole."""
    from benchmark.harness import scopes
    from tpu_probe import (compile_kernel_text, mosaic_signatures,
                           tpu_topology)

    assert GROUPED_SIGNATURE == scopes.GROUPED_MATMUL
    topo = tpu_topology(monkeypatch)
    n_rows, d, f, experts = shape
    k, n = (d, f) if product == "up" else (f, d)
    avals = (jax.ShapeDtypeStruct((n_rows, k), jnp.bfloat16),
             jax.ShapeDtypeStruct((experts, k, n), jnp.bfloat16),
             jax.ShapeDtypeStruct((experts,), jnp.int32))
    for fn, calls in ((_grouped_fwd, 1), (_grouped_bwd, 2)):
        txt = compile_kernel_text(topo, fn, avals, n_calls=calls)
        assert mosaic_signatures(txt) == [GROUPED_SIGNATURE] * calls


#: (entries of `back`, rows of the source, width, k) of a combine: the
#: tokens' T * k pairs into the row buffer (all the pairs in `olmoe-1chip`,
#: whose program keeps the `jnp` sum: the kernel compiles there all the same)
ROW_SUMS = {"smallthinker-1chip": (98304, 49152, 2560, 6),
            "dsv2lite-1chip": (49152, 12288, 2048, 6),
            "olmoe-1chip": (65536, 65536, 2048, 8)}

#: (operands, results) of `row_gather._row_form` (the limit, the rows) and of
#: `row_gather._sum_kernel` (rows, filled, deepest, limit; the row form)
ROW_SIGNATURES = [(2, 1), (5, 1)]


def _readers_signatures():
    from benchmark.harness import scopes
    from benchmark.layer_metrics import flash_roofline
    return set(flash_roofline.SIGNATURES) | {scopes.GROUPED_MATMUL,
                                             scopes.GROUPED_METADATA}


@pytest.mark.parametrize("cell", sorted(ROW_SUMS))
def test_row_sum_compiles_for_v5e(monkeypatch, cell):
    """`row_gather._kernel_sum` at a cell's shape: the row form and the sum
    are one Mosaic kernel each (the 393 KB of a cell's entries fit SMEM, the
    copy buffer VMEM), no sort is made for them (a sort of 98,304 keys takes
    the chip's compiler 20 s), and neither kernel has (operands, results) a
    reader of the benchmark knows a flash kernel or a grouped matmul by."""
    from horovod_tpu.ops import row_gather
    from tpu_probe import (compile_kernel_text, mosaic_signatures,
                           tpu_topology)

    topo = tpu_topology(monkeypatch)
    entries, n_rows, width, k = ROW_SUMS[cell]
    avals = (jax.ShapeDtypeStruct((n_rows, width), jnp.bfloat16),
             jax.ShapeDtypeStruct((entries,), jnp.int32),
             jax.ShapeDtypeStruct((), jnp.int32))
    txt = compile_kernel_text(
        topo, lambda x, back, n: row_gather._kernel_sum(x, back, k, n), avals,
        n_calls=2)
    assert mosaic_signatures(txt) == ROW_SIGNATURES
    assert not set(ROW_SIGNATURES) & _readers_signatures()
    assert " sort(" not in txt
    # the result has the tokens' shape: no padded copy is cut to size
    assert f"bf16[{entries // k},{width}]" in txt.split("ENTRY")[1].split(
        "ROOT")[1].split("custom-call")[0]


def test_an_expert_layers_row_kernels_carry_their_scopes(monkeypatch):
    """Two one-rank expert layers that hold 2 of 8 experts, a scan of
    checkpoints as the model's, forward and backward, compiled for the
    chip: the row kernels run in the combine and in the dispatch's backward
    pass, every one's `op_name`
    carries its scope (so `moe_dispatch_ms_per_step` reads them and
    `step_scopes.partition` books them), and none has a signature by which
    `flash_roofline`, `mla_flash_roofline` or `scopes.moe_parts` would take
    it for a flash kernel or a grouped matmul."""
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from benchmark.harness import hlo, scopes, step_scopes
    from benchmark.layer_metrics import mla_flash_roofline
    from horovod_tpu.parallel.moe import moe_ffn
    from tpu_probe import compile_kernel_text, tpu_topology

    topo = tpu_topology(monkeypatch)
    n_tokens, d, f, n_experts, n_local, k = 512, 256, 128, 8, 2, 2
    mesh = Mesh(np.array(topo.devices[:1]), ("ep",))

    def layer(x, weights):
        out, aux, _ = moe_ffn(x, weights["router"], weights["up"],
                              weights["down"], weights["gate"], top_k=k,
                              first_expert=2)
        return x + out, aux.sum()

    def loss(x, weights):    # two layers, as the model runs them
        x, aux = jax.lax.scan(jax.checkpoint(layer, prevent_cse=False), x,
                              weights)
        return x.astype(jnp.float32).sum() + aux.sum()

    def step(x, weights):
        return jax.shard_map(jax.value_and_grad(loss, argnums=(0, 1)),
                             mesh=mesh, in_specs=P(), out_specs=P(),
                             check_vma=False)(x, weights)

    def bf16(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16)

    avals = (bf16(n_tokens, d),
             {"router": bf16(2, d, n_experts), "up": bf16(2, n_local, d, f),
              "gate": bf16(2, n_local, d, f), "down": bf16(2, n_local, f, d)})
    # 11 grouped matmuls (3 forward, 2 of them again under remat, 6
    # backward); a row form and a sum in the combine, and in the dispatch's
    # backward pass
    txt = compile_kernel_text(topo, step, avals, n_calls=15)
    table = hlo.index(txt)
    parts = scopes.moe_parts(txt, table)
    booked = step_scopes.partition(txt, table)
    kernels = {name: (i.n_operands, len(i.results))
               for name, i in table.items() if i.is_mosaic_kernel}
    rows = {name: sig for name, sig in kernels.items()
            if parts.get(name) in ("dispatch", "combine")}
    assert sorted(rows.values()) == sorted(ROW_SIGNATURES * 2)
    assert {parts[name] for name in rows} == {"dispatch", "combine"}
    for name in rows:
        assert booked[name] == "moe." + parts[name]
    assert not set(rows.values()) & _readers_signatures()
    assert not set(rows) & set(mla_flash_roofline.flash_kernels(table))
    assert not set(rows) & set(scopes.grouped_kernels(table))
    # and the other eleven are what they were: the experts' products
    assert sorted(sig for name, sig in kernels.items()
                  if name not in rows) == [scopes.GROUPED_MATMUL] * 11


def _stack_traffic(txt, leaf_shapes, depth):
    """What a compiled step moves of the expert leaves around its layer
    scan: how many instructions, fused or not, slice an array of a leaf's
    shape out of a stack, add two arrays of a stack's shape, or fill one
    (the static count of docs/observability.md)."""
    import re

    def shaped(shapes):
        return "|".join(re.escape(",".join(map(str, s))) for s in shapes)

    leaf = shaped(leaf_shapes) + "|" + shaped((1,) + s for s in leaf_shapes)
    stack = shaped((depth,) + s for s in leaf_shapes)

    def count(shapes, opcodes):
        return len(re.findall(
            rf"= bf16\[(?:{shapes})\]\S* (?:{opcodes})\(", txt))

    return {"slices": count(leaf, "dynamic-slice"),
            "adds": count(stack, "add"),
            "fills": count(stack, "broadcast")}


def test_the_layer_scan_copies_no_expert_leaf_out_of_its_stack(monkeypatch):
    """The model's own loss and gradients over two scanned, checkpointed
    expert layers, compiled for the chip: the grouped matmuls read each
    layer's matrices in place in the stacked leaves, so no instruction
    slices an array of a leaf's shape out of a stack (the scan's `xs[l]` in
    front of a custom call was a copy: three leaves, the forward and the
    backward loop), nothing of a stack's shape is added, and nothing of it
    is filled but the three stacked gradients, as ever; the kernels are the
    eleven they were, each (7, 1) under `moe.experts`. With the stacks
    withheld from the products the same count finds the six copies."""
    from benchmark.harness import hlo, scopes
    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.parallel import MeshSpec, build_mesh, moe
    from tpu_probe import compile_kernel_text, tpu_topology

    topo = tpu_topology(monkeypatch)
    depth, d, f, n_experts = 2, 256, 128, 8
    cfg = tfm.TransformerConfig(
        vocab=512, d_model=d, n_heads=2, d_ff=f, n_layers=depth, max_seq=512,
        num_experts=n_experts, experts_per_token=2, load_balance_coef=0.01,
        router_z_coef=0.001, norm="rmsnorm", positions="rope", qk_norm=True,
        mlp="swiglu", attn="local", dtype=jnp.bfloat16, remat=True)
    mesh = build_mesh(MeshSpec(), topo.devices[:1])
    tokens = jax.ShapeDtypeStruct((1, 512), jnp.int32)
    avals = (jax.eval_shape(lambda k: tfm.init(k, cfg),
                            jax.random.PRNGKey(0)), tokens, tokens)

    def compiled_text():
        # 11 grouped matmuls: 3 forward, gate and up again under remat, 6
        # backward
        with jax.enable_x64(False):   # as the benchmark runs
            return compile_kernel_text(
                topo, tfm.build_loss_and_grads(cfg, mesh), avals, n_calls=11)

    leaves = [(n_experts, d, f), (n_experts, f, d)]
    txt = compiled_text()
    assert _stack_traffic(txt, leaves, depth) == {
        "slices": 0, "adds": 0, "fills": 3}
    table = hlo.index(txt)
    parts = scopes.moe_parts(txt, table)
    kernels = {name: (i.n_operands, len(i.results))
               for name, i in table.items() if i.is_mosaic_kernel}
    assert sorted(kernels.values()) == [scopes.GROUPED_MATMUL] * 11
    assert {parts.get(name) for name in kernels} == {"experts"}
    assert all(name.startswith("moe.experts") for name in kernels)

    grouped_matmul = moe.grouped_matmul
    monkeypatch.setattr(
        moe, "grouped_matmul",
        lambda rows, weights, plan, stack=None, layer=0:
        grouped_matmul(rows, weights, plan))
    assert _stack_traffic(compiled_text(), leaves, depth) == {
        "slices": 6, "adds": 0, "fills": 3}


HYBRID_RULE = (1, 30, 8192, 96, 192)   # its linear layers: keys | values


#: (operands, results) of the gated delta rule's Mosaic kernels: the forward
#: (q, k, v, b, beta -> o), with a gradient asked the forward with its four
#: residuals (W, U_0, T, the chunks' entry states) and the backward kernel
#: (ten arrays -> dq, dk, dv, db, dbeta)
GDN_SIGNATURES = {"fwd": [(5, 1)], "bwd": [(5, 5), (10, 5)]}


@pytest.mark.parametrize("name", ["fwd", "bwd"])
def test_gated_delta_rule_compiles_for_v5e(monkeypatch, name):
    """`ops/gated_delta.py` at `olmohybrid-1chip`'s shapes, keys 96 and
    values 192 wide, 128 chunks, six heads a grid step: forward and backward
    are Mosaic kernels and nothing else walks the sequence (no `while`).
    None of them can be taken for a flash kernel of the cell's full layer:
    `attn_flash_*` tell theirs by (operands, results) and by a first result
    of (30, 8,192, 128)."""
    from benchmark.harness import hlo
    from benchmark.layer_metrics import attn_flash_roofline, flash_roofline
    from horovod_tpu.ops.gated_delta import gated_delta_rule, heads_a_step
    from tpu_probe import (compile_kernel_text, mosaic_signatures,
                           tpu_topology)

    topo = tpu_topology(monkeypatch)
    b, h, s, dk, dv = HYBRID_RULE
    assert heads_a_step(h, dk, dv) == 6
    wide = jax.ShapeDtypeStruct((b, h, s, dk), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((b, h, s, dv), jnp.bfloat16)
    gate = jax.ShapeDtypeStruct((b, h, s), jnp.float32)

    def bwd(*args):
        return jax.grad(lambda *a: gated_delta_rule(*a).astype(
            jnp.float32).sum(), argnums=(0, 1, 2, 3, 4))(*args)

    want = GDN_SIGNATURES[name]
    txt = compile_kernel_text(topo, {"fwd": gated_delta_rule, "bwd": bwd}[
        name], (wide, wide, v, gate, gate), n_calls=len(want))
    assert mosaic_signatures(txt) == want
    assert " while(" not in txt
    assert not set(want) & set(flash_roofline.SIGNATURES)
    assert attn_flash_roofline.flash_kernels(hlo.index(txt),
                                             HYBRID_CELL) == {}


#: `kimilinear-1chip`'s rule: (batch, heads, tokens, key width, value width)
KIMI_RULE = (1, 32, 16384, 128, 128)


@pytest.mark.parametrize("name", ["fwd", "bwd"])
def test_the_per_channel_rule_compiles_for_v5e(monkeypatch, name):
    """`ops/gated_delta.py` with a decay per key channel at
    `kimilinear-1chip`'s shapes, 256 chunks, four heads a grid step: the
    blocks of eight rows, their lane-offset stores of T | P and the
    products of sixteen rows by the chunk compile for the chip; forward and
    backward are Mosaic kernels of the scalar form's signatures (with a
    gradient asked the forward writes W, U_0, T | P and the entry states;
    the backward reads ten arrays) and nothing else walks the sequence: g
    goes into the kernels as it comes and its cotangent comes out of one (no
    reduce-window, which a `jnp` running sum becomes on the chip);
    none can be taken for a flash kernel of the cell's MLA layer."""
    from benchmark.layer_metrics import flash_roofline
    from horovod_tpu.ops.gated_delta import gated_delta_rule, heads_a_step
    from tpu_probe import (compile_kernel_text, mosaic_signatures,
                           tpu_topology)

    topo = tpu_topology(monkeypatch)
    b, h, s, dk, dv = KIMI_RULE
    assert heads_a_step(h, dk, dv, per_channel=True) == 4
    wide = jax.ShapeDtypeStruct((b, h, s, dk), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((b, h, s, dv), jnp.bfloat16)
    decay = jax.ShapeDtypeStruct((b, h, s, dk), jnp.float32)
    beta = jax.ShapeDtypeStruct((b, h, s), jnp.float32)

    def bwd(*args):
        return jax.grad(lambda *a: gated_delta_rule(*a).astype(
            jnp.float32).sum(), argnums=(0, 1, 2, 3, 4))(*args)

    want = GDN_SIGNATURES[name]
    txt = compile_kernel_text(topo, {"fwd": gated_delta_rule, "bwd": bwd}[
        name], (wide, wide, v, decay, beta), n_calls=len(want))
    assert mosaic_signatures(txt) == want
    assert " while(" not in txt and "reduce-window" not in txt
    assert not set(want) & set(flash_roofline.SIGNATURES)


#: (operands, results) of the convolution's Mosaic kernels: the forward (the
#: rows of u before a tile, u, the taps -> y) and the backward (the rows
#: before, u, the taps, dy -> du and the partial sums of dw)
CONV_SIGNATURES = {"fwd": [(3, 1)], "bwd": [(4, 2)]}


@pytest.mark.parametrize("name", ["fwd", "bwd"])
@pytest.mark.parametrize("width,l2_scale", [(96, 96 ** -0.5), (192, None)],
                         ids=["keys-96-normed", "values-192"])
def test_causal_conv_compiles_for_v5e(monkeypatch, width, l2_scale, name):
    """`ops/causal_conv.py` at `olmohybrid-1chip`'s shapes, queries and keys
    96 wide with the norm and values 192 wide without: forward and backward
    are one Mosaic kernel each, nothing else walks the sequence, no float32
    array of u's size is made around them, and neither can be taken for a
    flash kernel, a grouped matmul or a kernel of the rule."""
    from benchmark.harness import hlo
    from benchmark.layer_metrics import attn_flash_roofline, flash_roofline
    from horovod_tpu.ops.causal_conv import causal_conv_silu
    from tpu_probe import (compile_kernel_text, mosaic_signatures,
                           tpu_topology)

    topo = tpu_topology(monkeypatch)
    b, h, s = HYBRID_RULE[:3]
    u = jax.ShapeDtypeStruct((b, h, s, width), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((h, width, 4), jnp.bfloat16)

    def fwd(u, w):
        return causal_conv_silu(u, w, l2_scale=l2_scale)

    def bwd(u, w):
        return jax.grad(lambda u, w: fwd(u, w).astype(jnp.float32).sum(),
                        argnums=(0, 1))(u, w)

    want = CONV_SIGNATURES[name]
    txt = compile_kernel_text(topo, {"fwd": fwd, "bwd": bwd}[name], (u, w),
                              n_calls=len(want))
    assert mosaic_signatures(txt) == want
    assert " while(" not in txt
    assert f"f32[{b},{h},{s},{width}]" not in txt
    taken = set(flash_roofline.SIGNATURES) | {GROUPED_SIGNATURE} \
        | set(sum(GDN_SIGNATURES.values(), []))
    assert not set(want) & taken
    assert attn_flash_roofline.flash_kernels(hlo.index(txt),
                                             HYBRID_CELL) == {}


def test_interpret_decision_is_shared_and_visible(monkeypatch):
    """One helper decides interpreter-vs-Mosaic for every kernel family;
    on the CPU suite it says "interpret", and flipping it flips the
    flash and the conv kernels together (what tpu_probe relies on)."""
    from horovod_tpu.ops import (_pallas, conv_block, conv_bn_backward,
                                 flash_attention as fa, grouped_matmul as gm)

    assert _pallas.interpret() is True
    for mod in (fa, conv_block, conv_bn_backward, gm):
        assert mod.pallas_call is _pallas.pallas_call
        assert not hasattr(mod, "_interpret")


#: `phi4flash-1chip`: (batch, seq, channels, states) of a state-space
#: layer's scan, and one softmax of differential attention: (batch, query
#: pairs, K/V pairs, seq, keys' width, values' width), a window of 512
PHI4_SCAN = (1, 8192, 5120, 16)

#: (operands, results) of the selective scan's Mosaic kernels: the forward
#: (c, delta, A, B and C along the lanes, D -> y), with a gradient asked the
#: forward with the tiles' entry states and the backward kernel (those, dy
#: and the entry states -> dc, ddelta, dA and the partial sums of dB, dC)
SCAN_SIGNATURES = {"fwd": [(6, 1)], "bwd": [(6, 2), (8, 5)]}


@pytest.mark.parametrize("name", ["fwd", "bwd"])
def test_selective_scan_compiles_for_v5e(monkeypatch, name):
    """`ops/selective_scan.py` at `phi4flash-1chip`'s shapes: 5,120 channels
    in five blocks of 1,024, sixteen states on the sublanes, 64 tiles of
    128 tokens; forward and backward are Mosaic kernels and nothing else
    walks the sequence. Its forward kernels share (operands, results) with
    flash's dq and dk/dv: the cell's flash reader tells its own by shape as
    well, and takes none of these."""
    from benchmark.harness import hlo
    from benchmark.layer_metrics import diff_flash_roofline
    from horovod_tpu.ops.selective_scan import selective_scan
    from tpu_probe import (compile_kernel_text, mosaic_signatures,
                           tpu_topology)

    topo = tpu_topology(monkeypatch)
    b, s, e, n = PHI4_SCAN
    avals = (jax.ShapeDtypeStruct((b, s, e), jnp.bfloat16),
             jax.ShapeDtypeStruct((b, s, e), jnp.float32),
             jax.ShapeDtypeStruct((e, n), jnp.float32),
             jax.ShapeDtypeStruct((b, s, n), jnp.bfloat16),
             jax.ShapeDtypeStruct((b, s, n), jnp.bfloat16),
             jax.ShapeDtypeStruct((e,), jnp.float32))

    def bwd(*args):
        return jax.grad(lambda *a: selective_scan(*a).astype(
            jnp.float32).sum(), argnums=range(6))(*args)

    want = SCAN_SIGNATURES[name]
    txt = compile_kernel_text(topo, {"fwd": selective_scan, "bwd": bwd}[
        name], avals, n_calls=len(want))
    assert mosaic_signatures(txt) == want
    assert " while(" not in txt
    assert diff_flash_roofline.flash_kernels(hlo.index(txt),
                                             PHI4_FLASH) == {}


#: `granite4h-1chip`'s scan: (batch, tokens, heads held, head width, states)
GRANITE_SCAN = (1, 4096, 64, 64, 128)
#: (operands, results) of the state-space dual scan's Mosaic kernels: the
#: forward (x, [dt, l] by columns, l by rows, B, C, D along the lanes -> y),
#: with a gradient asked the forward with the chunks' entry states and y in
#: float32, and the backward kernel (those and dy -> dx, [ddt, dl], dB and dC
#: a block of heads, dD a chunk)
SSD_SIGNATURES = {"fwd": [(6, 1)], "bwd": [(6, 3), (9, 5)]}


@pytest.mark.parametrize("name", ["fwd", "bwd"])
def test_ssd_scan_compiles_for_v5e(monkeypatch, name):
    """`ops/ssd_scan.py` at `granite4h-1chip`'s shapes: 64 heads of 64 in
    four blocks of 16, 128 states, 16 chunks of 256 tokens; forward and
    backward are Mosaic kernels and nothing else walks the sequence. None
    has a grouped matmul's (operands, results), by which the expert layer's
    readers tell theirs in the same step."""
    from benchmark.harness import hlo, scopes
    from horovod_tpu.ops.ssd_scan import ssd_scan
    from tpu_probe import (compile_kernel_text, mosaic_signatures,
                           tpu_topology)

    topo = tpu_topology(monkeypatch)
    b, s, h, p, n = GRANITE_SCAN
    avals = (jax.ShapeDtypeStruct((b, s, h * p), jnp.bfloat16),
             jax.ShapeDtypeStruct((b, s, h), jnp.float32),
             jax.ShapeDtypeStruct((h,), jnp.float32),
             jax.ShapeDtypeStruct((b, s, n), jnp.bfloat16),
             jax.ShapeDtypeStruct((b, s, n), jnp.bfloat16),
             jax.ShapeDtypeStruct((h,), jnp.float32))

    def bwd(*args):
        return jax.grad(lambda *a: ssd_scan(*a).astype(jnp.float32).sum(),
                        argnums=tuple(range(6)))(*args)

    want = SSD_SIGNATURES[name]
    txt = compile_kernel_text(topo, {"fwd": ssd_scan, "bwd": bwd}[name],
                              avals, n_calls=len(want))
    assert mosaic_signatures(txt) == want
    assert " while(" not in txt
    assert scopes.grouped_kernels(hlo.index(txt)) == {}


def test_the_fused_flash_backwards_signature_is_no_readers(monkeypatch):
    """The flash backward as one kernel (PR 55) takes q, k, v, o, do, lse
    (and the lse cotangent of a ring hop's chunk) and returns dq, dk, dv:
    (6, 3) (`test_flash_tpu_aot.SIGNATURES`), or (7, 3), compiled here. Neither is a signature a reader of the benchmark
    tells a kernel by today (`flash_roofline.SIGNATURES`, the grouped
    matmul's, its metadata's) nor a row mover's, so no reader takes it for
    another kernel and `flash_roofline.read` gives None where it meets it.
    (6, 3) IS the state-space dual scan's forward-with-residuals kernel's
    (`SSD_SIGNATURES`, `granite4h-1chip`): a reader that learns it must tell
    flash's by its first result's rows and width too (PERF.md §7)."""
    from horovod_tpu.ops.flash_attention import flash_attention_chunk
    from test_flash_tpu_aot import LM_CELLS, SIGNATURES
    from tpu_probe import (compile_kernel_text, mosaic_signatures,
                           tpu_topology)

    topo = tpu_topology(monkeypatch)
    q = jax.ShapeDtypeStruct(LM_CELLS, jnp.bfloat16)

    def chunk_bwd(q, k, v):
        def loss(q, k, v):
            o, lse = flash_attention_chunk(q, k, v, causal=True)
            return o.astype(jnp.float32).sum() + lse.sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    txt = compile_kernel_text(topo, chunk_bwd, (q, q, q), n_calls=2)
    assert mosaic_signatures(txt) == [(3, 2), (7, 3)]
    for backward in ((6, 3), (7, 3)):
        assert backward not in _readers_signatures()
        assert backward not in ROW_SIGNATURES + [GROUPED_SIGNATURE]
    assert SIGNATURES["bwd"] == [(3, 2), (6, 3)]
    assert (6, 3) in SSD_SIGNATURES["bwd"]


@pytest.mark.parametrize("name, reduces", [("tiny-lm-1chip", False),
                                           ("tiny-lm-dp4", True)])
def test_the_tiny_lm_cells_steps_hold_three_flash_kernels(monkeypatch, name,
                                                          reduces):
    """`tests/benchmark/test_benchmark_aot.py`'s two LM cases, line for line
    (that file is the benchmark's and holds the step to the FOUR kernels it
    had until PR 55; `tests/conftest.py` expects those two to fail): each
    path's `abstract_step` of the tests' tiny cells compiles for the
    described v5e without a problem, with three Mosaic kernels (the flash
    forward, its remat repeat and the one backward kernel), an all-reduce
    across the four chips and none on one."""
    import os

    from benchmark import aot_check
    from benchmark.harness import peaks, spec
    from tpu_probe import _no_persistent_cache, tpu_topology

    topo = tpu_topology(monkeypatch)
    tiny = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "benchmark", "fixtures", "tiny")
    cell = spec.load_cell(name, root=tiny)
    hbm = peaks.for_kind(aot_check.DEVICE_KIND).hbm_bytes
    with jax.enable_x64(False), _no_persistent_cache():
        found, problems = aot_check.check_cell(cell, topo.devices, hbm)
    assert problems == [], found
    assert "3 tpu_custom_call" in found
    assert ("all-reduces 0 bytes" not in found) == reduces
    assert f"({cell.chips} chip(s))" in found
