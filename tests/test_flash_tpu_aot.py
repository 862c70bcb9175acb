"""The flash kernels (ops/flash_attention.py) compiled by the chip's own
compiler, without a chip, beside `tests/test_kernels_tpu_aot.py` (the other
kernels): Mosaic accepts the forward and the fused backward kernel (PR 55;
the dq and dk/dv kernels it stands for past the VMEM budget too) for a
described v5e at the shapes the main path uses — the flagship LM's
(B*H, S, dh) = (12*16, 1024, 128), the benchmark's LM cells'
(4*16, 2048, 128) and `olmoe-1chip`'s (2*16, 4096, 128), whose diagonal
blocks are walked in tiles, the long-context S=8192, unequal widths, a window
and grouped K/V — the forward's custom call keeps the operands and results
the benchmark's readers know it by, the backward's (6 operands, 3 results)
are none they know yet (PERF.md §7), the grids hold only the blocks the mask
holds, and `jax_enable_x64` does not matter to a kernel compiled for the
chip; and `olmohybrid-1chip`'s whole train step at full size. Skipped only
where the topology cannot be described (tests/tpu_probe.py)."""

import jax
import jax.numpy as jnp
import pytest

from horovod_tpu.ops.flash_attention import flash_attention


FLAGSHIP = (12, 16, 1024, 128)   # chip_smoke.py flagship LM


LM_CELLS = (4, 16, 2048, 128)    # lm-1chip, lm-dp4 per chip


OLMOE_CELL = (2, 16, 4096, 128)  # olmoe-1chip


LONG = (1, 16, 8192, 128)


#: (operands, results) of the forward (q, k, v -> o, lse) and the fused
#: backward (q, k, v, o, do, lse -> dq, dk, dv) custom calls; past the VMEM
#: budget the backward is dq (6, 1) and dk/dv (6, 2), as until PR 55.
SIGNATURES = {"fwd": [(3, 2)], "bwd": [(3, 2), (6, 3)],
              "bwd-split": [(3, 2), (6, 1), (6, 2)]}


def _fwd(q, k, v):
    return flash_attention(q, k, v, causal=True)


def _bwd(q, k, v):
    return jax.grad(
        lambda q, k, v: _fwd(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))(q, k, v)


CASES = [
    pytest.param(name, shape, False, id=f"{name}-{sid}")
    for shape, sid in ((FLAGSHIP, "S1024"), (LM_CELLS, "S2048"),
                       (OLMOE_CELL, "S4096"), (LONG, "S8192"))
    for name in ("fwd", "bwd")
] + [
    # x64 on: one case, the one that compiles both kernels
    pytest.param("bwd", FLAGSHIP, True, id="bwd-S1024-x64"),
    # the two backward kernels, as a shape past the budget takes them
    pytest.param("bwd-split", LM_CELLS, False, id="bwd-split-S2048"),
]


@pytest.mark.parametrize("name,shape,x64", CASES)
def test_flash_attention_compiles_for_v5e(monkeypatch, name, shape, x64):
    from tpu_probe import (compile_kernel_text, mosaic_signatures,
                           tpu_topology)

    topo = tpu_topology(monkeypatch)
    if name == "bwd-split":
        from horovod_tpu.ops import flash_attention as fa
        monkeypatch.setattr(fa, "_FUSED_VMEM_LIMIT", 0)
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    want = SIGNATURES[name]
    # (a function of its own: a trace of `_bwd` at this shape may be cached)
    fn = {"fwd": _fwd, "bwd": _bwd, "bwd-split": lambda *a: _bwd(*a)}[name]
    with jax.enable_x64(x64):
        txt = compile_kernel_text(topo, fn, (q, q, q), n_calls=len(want))
    assert mosaic_signatures(txt) == want


@pytest.mark.parametrize("name", ["fwd", "bwd"])
def test_flash_attention_at_unequal_widths_compiles_for_v5e(monkeypatch,
                                                            name):
    """`dsv2lite-1chip`'s latent attention: 192-wide queries and keys,
    128-wide values (PR 30). The kernels take two lane tiles a row and are
    given 32 MiB of VMEM (inside the train step the dk/dv kernel needed 17.2,
    which `benchmark.aot_check` of the cell guards; alone it fit 16), the
    fused backward the 11 MiB of its accumulators and output blocks more."""
    from tpu_probe import (compile_kernel_text, mosaic_signatures,
                           tpu_topology)

    topo = tpu_topology(monkeypatch)
    wide = jax.ShapeDtypeStruct(OLMOE_CELL[:3] + (192,), jnp.bfloat16)
    v = jax.ShapeDtypeStruct(OLMOE_CELL, jnp.bfloat16)
    want = SIGNATURES[name]
    txt = compile_kernel_text(topo, {"fwd": _fwd, "bwd": _bwd}[name],
                              (wide, wide, v), n_calls=len(want))
    assert mosaic_signatures(txt) == want
    assert "33554432" in txt
    if name == "bwd":
        assert str((32 + 11) * 2 ** 20) in txt


HYBRID_CELL = (1, 30, 8192, 128)   # olmohybrid-1chip's full layer


@pytest.mark.parametrize("name", ["fwd", "bwd"])
def test_flash_attention_at_the_hybrid_cells_shape_compiles_for_v5e(
        monkeypatch, name):
    from tpu_probe import (compile_kernel_text, mosaic_signatures,
                           tpu_topology)

    topo = tpu_topology(monkeypatch)
    q = jax.ShapeDtypeStruct(HYBRID_CELL, jnp.bfloat16)
    want = SIGNATURES[name]
    txt = compile_kernel_text(topo, {"fwd": _fwd, "bwd": _bwd}[name],
                              (q, q, q), n_calls=len(want))
    assert mosaic_signatures(txt) == want


def test_the_hybrid_cells_step_compiles_at_full_size_and_fits(monkeypatch):
    """`benchmark.aot_check olmohybrid-1chip` as a test: the whole train
    step at the published widths, 1 x 8,192 tokens, for a described v5e: it
    compiles, leaves `HEADROOM_GIB` of the chip's memory and holds the
    full layer's flash kernels and the three linear layers' (half a minute
    here)."""
    from benchmark import aot_check
    from benchmark.harness import peaks, spec
    from tpu_probe import _no_persistent_cache, tpu_topology

    topo = tpu_topology(monkeypatch)
    cell = spec.load_cell("olmohybrid-1chip")
    hbm = peaks.for_kind(aot_check.DEVICE_KIND).hbm_bytes
    with jax.enable_x64(False), _no_persistent_cache():
        found, problems = aot_check.check_cell(cell, topo.devices, hbm)
    assert problems == [], found
    # 3: the flash forward, its remat repeat (each layer of a period is its
    # own checkpoint behind a barrier) and the one backward kernel (dk/dv
    # and dq until PR 55); 9: for each of the
    # three linear layers the gated delta rule's forward, its remat repeat
    # (which writes the backward's residuals) and its backward kernel; 27:
    # for each of them the convolution of q, of k and of v, each forward,
    # repeated under remat and backward (`ops/causal_conv.py`, PR 35)
    assert "39 tpu_custom_call" in found and "(1 chip(s))" in found
    need = float(found.split("needs ")[1].split(" GiB")[0])
    # over a quarter of the chip's memory, and what PERF.md says (14.29
    # while the "dots" policy kept the `jnp` rule's products, until PR 33;
    # 10.95 while the convolution's float32 passes went through HBM, until
    # PR 35)
    assert 0.25 * hbm / 2 ** 30 < need == pytest.approx(10.53, abs=0.15)


PHI4_FLASH = (1, 20, 10, 8192, 64, 128)


PHI4_WINDOW = 512


@pytest.mark.parametrize("window", [None, PHI4_WINDOW],
                         ids=["full", "window512"])
@pytest.mark.parametrize("name", ["fwd", "bwd"])
def test_grouped_windowed_flash_compiles_for_v5e(monkeypatch, name, window):
    """One softmax of `phi4flash-1chip`'s differential attention: 20 query
    pairs of 64 over 10 K/V pairs, values 128 wide, 8,192 tokens, with and
    without the 512-key window. The forward's custom call keeps the operands
    and results the readers know it by; the fused backward's (6, 3) is no
    kind they know, so the cell's reader finds the forward alone and skips
    the backward without raising (until a `benchmark` PR teaches
    `flash_roofline.SIGNATURES` the third kind: PERF.md §7)."""
    from benchmark.harness import hlo
    from benchmark.layer_metrics import diff_flash_roofline
    from tpu_probe import (compile_kernel_text, mosaic_signatures,
                           tpu_topology)

    topo = tpu_topology(monkeypatch)
    b, h, g, s, dk, dv = PHI4_FLASH
    avals = (jax.ShapeDtypeStruct((b, h, s, dk), jnp.bfloat16),
             jax.ShapeDtypeStruct((b, g, s, dk), jnp.bfloat16),
             jax.ShapeDtypeStruct((b, g, s, dv), jnp.bfloat16))

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window)

    def bwd(q, k, v):
        return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    want = SIGNATURES[name]
    txt = compile_kernel_text(topo, {"fwd": fwd, "bwd": bwd}[name], avals,
                              n_calls=len(want))
    assert mosaic_signatures(txt) == want
    kinds = diff_flash_roofline.flash_kernels(hlo.index(txt), PHI4_FLASH)
    assert sorted(kinds.values()) == ["forward"]
    if name == "bwd":   # dq at the query heads' count, dk and dv the K/V's
        (backward,) = [i for i in hlo.index(txt).values()
                       if i.is_mosaic_kernel and len(i.results) == 3]
        assert [dims for _, dims in backward.results] == [
            (b * h, s, dk), (b * g, s, dk), (b * g, s, dv)]


#: The shapes the benchmark's cells run the flash kernels at: (query heads,
#: K/V heads, seq, keys' width, values' width, window), a chip's batch
#: folded into the heads as the kernels see it
CELL_FLASH = {
    "lm-1chip": (64, 64, 2048, 128, 128, None),           # and lm-dp4
    "olmoe-1chip": (32, 32, 4096, 128, 128, None),
    "dsv2lite-1chip": (32, 32, 4096, 192, 128, None),
    "olmohybrid-1chip": (30, 30, 8192, 128, 128, None),
    "phi4flash-1chip-full": PHI4_FLASH[1:] + (None,),
    "phi4flash-1chip-window": PHI4_FLASH[1:] + (PHI4_WINDOW,),
    # the three whose accumulators over 16,384 tokens take the most VMEM (a
    # windowed layer of the first and `granite4h-1chip`'s (16 | 4, 4,096,
    # 128) compile in their cells' `benchmark.aot_check`)
    "smallthinker-1chip-full": (28, 4, 16384, 128, 128, None),
    "kimilinear-1chip": (32, 32, 16384, 192, 128, None),
    "lfm2moe-1chip": (32, 8, 16384, 64, 64, None),
}


def _pallas_grids(jaxpr, found=None):
    """The grid of every `pallas_call` in a jaxpr, in the order met."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(tuple(eqn.params["grid_mapping"].grid))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)    # a ClosedJaxpr's
                if hasattr(sub, "eqns"):
                    _pallas_grids(sub, found)
    return found


@pytest.mark.parametrize("cell", list(CELL_FLASH))
def test_the_cells_flash_grids_hold_only_the_blocks_the_mask_holds(
        monkeypatch, cell):
    """At each cell's shape the forward and the fused backward kernel
    compile for v5e (its accumulators and output blocks over the sequence
    fit the VMEM it asks for: 48 MiB + 16 at 16,384 tokens and a group), with
    (3 | 6 operands and 2 | 3 results: the grid of held blocks took no
    scalar-prefetch operand), and their grids are (heads, held pairs) and
    (K/V heads, held pairs x group): 3 of 4 block pairs at 2,048, 10 of 16
    at 4,096, 36 of 64 at 8,192, 15 of 64 under the 512-token window, 136 of
    256 at 16,384."""
    from tpu_probe import (compile_kernel_text, mosaic_signatures,
                           tpu_topology)

    topo = tpu_topology(monkeypatch)
    h, g, s, dk, dv, window = CELL_FLASH[cell]
    avals = (jax.ShapeDtypeStruct((1, h, s, dk), jnp.bfloat16),
             jax.ShapeDtypeStruct((1, g, s, dk), jnp.bfloat16),
             jax.ShapeDtypeStruct((1, g, s, dv), jnp.bfloat16))

    def bwd(q, k, v):
        return jax.grad(lambda *a: flash_attention(
            *a, causal=True, window=window).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    txt = compile_kernel_text(topo, bwd, avals, n_calls=2)
    assert mosaic_signatures(txt) == SIGNATURES["bwd"]
    held = {(2048, None): 3, (4096, None): 10, (8192, None): 36,
            (8192, PHI4_WINDOW): 15, (16384, None): 136}[s, window]
    assert sorted(_pallas_grids(jax.make_jaxpr(bwd)(*avals).jaxpr)) == \
        sorted([(h, held), (g, held * (h // g))])

