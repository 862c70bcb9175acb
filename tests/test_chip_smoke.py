"""chip_smoke.py rehearsed on the CPU (on-chip-measurement guide §2,
rehearsals 1 and 2): every phase is a plain function of its sizes and
runs here at a tiny size, the one-chip ones on one virtual device, the
cross-chip ones on four. What only the chip can show — Mosaic-compiled
kernels, HBM, times — is asserted by the script itself, which refuses
to report success off the TPU; that refusal is tested here too.
"""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
import horovod_tpu as hvd  # noqa: E402
from horovod_tpu.models import resnet, transformer as tfm  # noqa: E402
from horovod_tpu.parallel.mesh import MeshSpec  # noqa: E402

TINY_LM = tfm.TransformerConfig(
    vocab=128, d_model=32, n_heads=2, d_ff=64, n_layers=1, max_seq=64,
    attn="flash", dtype=jnp.bfloat16, remat=True)


@pytest.fixture()
def smoke(request):
    """hvd over the first N virtual devices, 32-bit mode (the script
    refuses x64), one CompileLog."""
    hvd.shutdown()
    with jax.enable_x64(False):
        hvd.init(devices=jax.devices()[:request.param])
        yield chip_smoke.CompileLog()
        hvd.shutdown()


one_chip = pytest.mark.parametrize("smoke", [1], indirect=True)
four_chips = pytest.mark.parametrize("smoke", [4], indirect=True)


# ------------------------------------------------------- phases, tiny sizes

@one_chip
def test_phase_eager_api_one_rank(smoke, capsys):
    chip_smoke.eager_api(smoke, n=4096)
    assert "agree with numpy (1 rank(s)" in capsys.readouterr().out


@four_chips
def test_phase_eager_api_stacked_ranks(smoke, capsys):
    chip_smoke.eager_api(smoke, n=4096)
    assert "agree with numpy (4 rank(s)" in capsys.readouterr().out


@one_chip
def test_phase_flash_kernel(smoke, capsys):
    chip_smoke.flash_kernel(smoke, shape=(1, 2, 64, 32),
                            wide=(1, 2, 64, 48, 32))
    out = capsys.readouterr().out
    # both shapes, each with its forward and its one backward kernel alone
    assert "shape (1, 2, 64, 32) bf16" in out
    assert "shape (1, 2, 64, 48, 32) bf16" in out
    assert out.count(", backward ") == out.count(": forward ") == 2
    # the backward pass makes q.k, exp and do.v once a block pair
    assert out.count("backward_products 5 (score-sized products a block "
                     "pair of the backward pass: 5 in one kernel, ") == 2
    # off the TPU the kernel is interpreted, and the phase says so
    assert "interpret=True, 0 tpu_custom_call" in out
    # S=64 is one block no tile divides: all of it computed, half of it used
    assert "causal_tile_share 2.0000" in out
    # and no grid step that computes nothing, at either shape
    assert out.count("grid_step_share 1.0000 (grid steps a head runs") == 2
    # one block of 64 rows: its one strip is no longer than 256
    assert out.count("row_strip_share 1.0000 (the forward's score entries "
                     "in strips of 256 rows or fewer)") == 2


@one_chip
def test_phase_flash_kernel_at_two_blocks_walks_the_whole_one_in_strips(
        smoke, capsys):
    """Two blocks of 1,024: the one under the diagonal is a whole block,
    which the forward walks in four strips of 256 rows (`row_strip_share`
    1.0, PR 49; 0.5556 while it was one strip), and the two on the diagonal
    compute 9/8 of the causal half."""
    chip_smoke.flash_kernel(smoke, shape=(1, 1, 2048, 8),
                            wide=(1, 1, 2048, 16, 8))
    out = capsys.readouterr().out
    assert out.count("causal_tile_share 1.1250") == 2
    assert out.count("grid_step_share 1.0000 (grid steps a head runs") == 2
    assert out.count("row_strip_share 1.0000 (the forward's score entries "
                     "in strips of 256 rows or fewer)") == 2


@one_chip
def test_phase_grouped_kernel(smoke, capsys):
    chip_smoke.grouped_kernel(smoke, shapes=((96, 16, 24, 4, 96),
                                             (64, 16, 24, 2, 30)))
    out = capsys.readouterr().out
    assert "96 rows x (4, 16, 24) bf16, 96 rows routed" in out
    assert "64 rows x (2, 16, 24) bf16, 30 rows routed" in out
    assert out.count("agree with lax.ragged_dot and its vjp") == 2
    assert out.count("towards the weights ") == 2
    # fewer rows than a row tile are one tile, visited once a group
    assert "visit_share 4.0000" in out and "visit_share 2.0000" in out
    assert "interpret=True, 0 tpu_custom_call" in out


@one_chip
def test_phase_gated_delta_scan(smoke, capsys):
    chip_smoke.gated_delta_scan(smoke, shape=(1, 2, 200, 16, 32), checked=100)
    out = capsys.readouterr().out
    assert "[gated delta rule] 1 x 200 tokens x 2 heads, 16 | 32" in out
    assert "chunk 64, 4 chunks a sequence" in out
    assert "the solve by 4 panels, a chunk and head: 3 exact products (18 " \
        "bf16 passes), 88 lane broadcasts, 60 steps; 2 heads a grid step " \
        "(0.19 MiB of VMEM asked" in out
    assert "interpret=True, tpu_custom_call in the compiled forward 0, " \
        "forward + backward 0" in out
    assert "from the token-by-token recurrence" in out
    assert "forward + backward" in out
    assert "least time" not in out     # no share of a peak off the TPU


@one_chip
def test_phase_kda_scan(smoke, capsys):
    chip_smoke.kda_scan(smoke, shape=(1, 2, 200, 16, 16), checked=100)
    out = capsys.readouterr().out
    assert "[delta rule, a decay a channel] 1 x 200 tokens x 2 heads, " \
        "16 | 16" in out
    assert "chunk 64, 4 chunks a sequence" in out
    assert "2 heads a grid step (" in out
    assert "its decayed products by 7 levels of a halving, forward / " \
        "backward a chunk and head: 7 / 14 products, 48 / 48 exp " \
        "registers, 0 / 0 lane reductions, 0 / 0 lane broadcasts; g's " \
        "running sums inside the kernels, 6 / 12 doubling steps, 48 / 96 " \
        "rolled registers, 0 / 0 products; the " \
        "solve by 4 panels, a chunk and head: 3 exact products (18 bf16 " \
        "passes), 88 lane broadcasts, 60 steps; 2 heads a grid step (" in out
    assert "interpret=True, tpu_custom_call in the compiled forward 0, " \
        "forward + backward 0" in out
    assert "from the token-by-token recurrence" in out
    assert "least time" not in out     # no share of a peak off the TPU
    assert chip_smoke.kda_scan in chip_smoke.PHASES[1][1]


@one_chip
def test_phase_causal_conv_pass(smoke, capsys):
    chip_smoke.causal_conv_pass(smoke, shape=(2, 3, 150),
                                widths=((24, 24 ** -0.5), (7, None)))
    out = capsys.readouterr().out
    assert "[causal conv] 2 x 150 tokens x 3 heads, bf16, interpret=True, " \
        "0 recompiles after a first call; width 24 normed: tiles of 256 " \
        "tokens, 3 heads a grid step, tpu_custom_call in the compiled " \
        "forward 0, forward + backward 0, from the jnp form y " in out
    assert "; width 7: tiles of 256 tokens" in out
    assert out.count(" du ") == 2 and out.count(" dw ") == 2
    assert "HBM rate" not in out     # no share of a peak off the TPU


@one_chip
def test_phase_row_sum_pass(smoke, capsys):
    chip_smoke.row_sum_pass(smoke, shapes=((64, 256, 2, 8, 2),
                                           (32, 256, 2, 4, 4)))
    out = capsys.readouterr().out
    assert "[row sum] bf16, interpret=True, 0 recompiles after a first " \
        "call; 64 tokens x 256, k 2, 2 of 8 experts held: " in out
    assert " of 128 entries count into a buffer of 128 rows, moved_share 0." \
        in out
    # every expert held: the buffer is all the pairs, every entry counts
    assert "32 tokens x 256, k 2, 4 of 4 experts held: 64 of 64 entries " \
        "count into a buffer of 64 rows, moved_share 1.0000" in out
    assert out.count("the same bits as the jnp form") == 2
    assert out.count(" ns a counted row), jnp ") == 2
    assert "tpu_custom_call in the compiled kernel 0" in out
    assert "HBM rate" not in out     # no share of a peak off the TPU


@one_chip
def test_phase_embed_grad_pass(smoke, capsys):
    chip_smoke.embed_grad_pass(smoke, shapes=((600, 128, 97), (64, 256, 40)))
    out = capsys.readouterr().out
    assert "[embed grad] bf16, 0 recompiles after a first call; 600 tokens " \
        "x 128 into 97 rows: chunks of 256, scatters 0 (plain indexing's " \
        "backward: 1), slot_share 0." in out
    assert "; 64 tokens x 256 into 40 rows: chunks of 256, scatters 0 " in out
    assert out.count("the float32 scatter-add rounded once, bit for bit") == 2
    assert out.count(" ns a token), scatter-add ") == 2
    assert "HBM rate" not in out     # no share of a peak off the TPU


@one_chip
def test_phase_ssd_scan_pass(smoke, capsys):
    chip_smoke.ssd_scan_pass(smoke, shape=(1, 48, 4, 8, 8), checked=32)
    out = capsys.readouterr().out
    assert "[ssd scan] 1 x 48 tokens x 4 heads of 8 x 8 states" in out
    assert "chunk 48, 1 chunks a sequence, 4 heads a grid step, the " \
        "chunked form's multiply-adds " in out
    assert "x the recurrent form's; interpret=True, 0 recompiles after a " \
        "first call, tpu_custom_call in the compiled forward 0, forward + " \
        "backward 0" in out
    assert "the first 32 tokens from the token-by-token form y " in out
    assert all(f" {name} " in out for name in
               ("dx", "ddt", "da_log", "dB", "dC", "dD"))
    assert "least time" not in out     # no share of a peak off the TPU


@one_chip
def test_phase_selective_scan_pass(smoke, capsys):
    chip_smoke.selective_scan_pass(smoke, shape=(1, 64, 48, 8), checked=32)
    out = capsys.readouterr().out
    assert "[selective scan] 1 x 64 tokens x 48 channels x 8 states" in out
    assert "tiles of 64 tokens, 1 a sequence; interpret=True, 0 recompiles " \
        "after a first call, tpu_custom_call in the compiled forward 0, " \
        "forward + backward 0" in out
    assert "the first 32 tokens from the token-by-token form y " in out
    assert all(f" {name} " in out for name in
               ("dc", "ddelta", "dA", "dB", "dC", "dD"))
    assert "least time" not in out     # no share of a peak off the TPU


@one_chip
def test_phase_windowed_grouped_flash(smoke, capsys):
    chip_smoke.windowed_grouped_flash(smoke, shape=(1, 4, 2, 128, 8, 16),
                                      window=24, checked=64)
    out = capsys.readouterr().out
    assert "[grouped flash] 1 x 128 tokens, 4 query heads over 2 K/V heads, " \
        "8 | 16, bf16, interpret=True, 0 recompiles after a first call; " \
        "window 24: window_tile_share " in out
    assert "; no window: causal_tile_share " in out
    assert out.count(", grid_step_share 1.0000, row_strip_share 1.0000, "
                     "tpu_custom_call") == 2
    assert out.count("forward 0, forward + backward 0") == 2
    assert out.count(" dq ") == 2 and out.count(" dv ") == 2


@one_chip
def test_phase_flagship_lm(smoke, capsys):
    chip_smoke.flagship_lm(smoke, cfg=TINY_LM, batch=4, seq=64, steps=5)
    out = capsys.readouterr().out
    assert "0 recompiles after step 1" in out
    assert "tokens/s" in out and "not reported" in out  # CPU: no HBM stat


@one_chip
def test_phase_resnet_eager(smoke, capsys, monkeypatch):
    monkeypatch.setitem(resnet.STAGE_BLOCKS, 5, (1, 0, 0, 0))
    chip_smoke.resnet50_eager(smoke, batch=4, image=32, depth=5, steps=5)
    out = capsys.readouterr().out
    assert "resnet5 B4 32px bf16 eager" in out and "images/s" in out


@four_chips
def test_phase_single_controller_lm(smoke, capsys):
    # meshes and sharding rules are what this rehearses: plain attention
    # (the interpreted flash kernel ran in the one-device test above)
    chip_smoke.single_controller_lm(
        smoke, cfg=dataclasses.replace(TINY_LM, attn="local"), batch=4,
        seq=64, steps=3)
    out = capsys.readouterr().out
    for mesh in ("dp=4", "dp=2,tp=2"):
        assert f"{mesh}] losses match the one-device run" in out


def test_phase_launcher_one_process_per_device(monkeypatch, capsys):
    """The README launch, -np 2 on the CPU. The phase insists on a parent
    that has not touched JAX (a chip belongs to one process); this parent
    has, and on the CPU it does not matter — so the test says it has not."""
    from jax._src import xla_bridge

    log = chip_smoke.CompileLog()
    with pytest.raises(AssertionError, match="already holds a JAX backend"):
        chip_smoke.launcher_one_process_per_chip(log, np_=2, platform="cpu")
    monkeypatch.setattr(xla_bridge, "backends_are_initialized",
                        lambda: False)
    monkeypatch.setenv("XLA_FLAGS", "")  # workers: one CPU device each
    chip_smoke.launcher_one_process_per_chip(log, np_=2, platform="cpu")
    out = capsys.readouterr().out
    assert out.count("SMOKE_WORKER_OK") == 2
    assert "2 workers, one cpu device each" in out


def test_losses_must_be_finite_and_falling():
    chip_smoke.check_losses("x", [2.0, 1.5, 1.0])
    for bad in ([1.0, 1.0], [1.0, float("nan")], [1.0, 2.0]):
        with pytest.raises(AssertionError):
            chip_smoke.check_losses("x", bad)


# ------------------------------------------------- the script as a whole

def test_script_fails_without_a_tpu():
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                         capture_output=True, text=True, timeout=300,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "not on a TPU" in out.stderr


def test_preflight_refuses_emulation(monkeypatch):
    monkeypatch.setenv("HOROVOD_TPU_EMULATE_RANKS", "4")
    with jax.enable_x64(False), \
            pytest.raises(SystemExit, match="never emulates"):
        chip_smoke.preflight(1)


def test_preflight_refuses_x64_and_the_cpu():
    with jax.enable_x64(True), pytest.raises(SystemExit, match="x64"):
        chip_smoke.preflight(1)
    with jax.enable_x64(False), \
            pytest.raises(SystemExit, match="not on a TPU"):
        chip_smoke.preflight(1)
    hvd.shutdown()


def _fake_run(monkeypatch, *phases):
    """main() with the chip pretended there and stand-in phases."""
    monkeypatch.setattr(chip_smoke, "preflight", lambda chips: None)
    monkeypatch.setattr(chip_smoke, "PHASES", {1: ((), phases)})
    chip_smoke.main([])


def _good(log):
    print("phase ran")


def _bad(log):
    raise RuntimeError("synthetic phase failure")


def test_main_prints_ok_last_when_every_phase_passes(monkeypatch, capsys):
    _fake_run(monkeypatch, _good, _good)
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": len(jax.devices())}}


def test_main_fails_when_one_phase_raises(monkeypatch, capsys):
    """Nothing catches a phase's exception: it leaves main() (so the
    script exits non-zero), nothing runs after it, no ok line."""
    with pytest.raises(RuntimeError, match="synthetic phase failure"):
        _fake_run(monkeypatch, _good, _bad, _good)
    out = capsys.readouterr().out
    assert out.count("phase ran") == 1
    assert '"ok"' not in out


def test_four_chip_run_holds_no_single_chip_phase():
    def flat(chips):
        return {p for group in chip_smoke.PHASES[chips] for p in group}
    assert flat(4) and flat(1)
    assert flat(4).isdisjoint(flat(1))
    # the launcher phase runs before this process touches JAX
    assert chip_smoke.PHASES[4][0] == (
        chip_smoke.launcher_one_process_per_chip,)
    assert chip_smoke.PHASES[1][0] == ()


# ------------------------------------------------------- what it rests on

def test_compile_cache_resolver(monkeypatch, tmp_path):
    from horovod_tpu.common.config import Config
    from horovod_tpu.core import topology

    for var in ("JAX_COMPILATION_CACHE_DIR", "HOROVOD_TPU_COMPILE_CACHE"):
        monkeypatch.delenv(var, raising=False)
    def resolve(platform):
        return topology.compile_cache_dir(Config.from_env(), platform)

    assert resolve("tpu") == (os.path.join(REPO, ".jax_cache"), True)
    assert resolve("cpu") == (None, False)  # the default is for chips
    monkeypatch.setenv("HOROVOD_TPU_COMPILE_CACHE", str(tmp_path / "h"))
    for platform in ("tpu", "cpu"):
        assert resolve(platform) == (str(tmp_path / "h"), True)
    # JAX's own variable wins and is left to JAX: nothing to set in code
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "j"))
    for platform in ("tpu", "cpu"):
        assert resolve(platform) == (str(tmp_path / "j"), False)


def test_init_sets_the_cache_only_where_the_resolver_says(monkeypatch,
                                                         tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, hvd.init() makes no
    jax_compilation_cache_dir update of its own; with only
    HOROVOD_TPU_COMPILE_CACHE set, it makes exactly that one."""
    seen = []
    real = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda k, v: seen.append((k, v)) if k == "jax_compilation_cache_dir"
        else real(k, v))
    hvd.shutdown()
    monkeypatch.setenv("HOROVOD_TPU_COMPILE_CACHE", str(tmp_path / "h"))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "j"))
    hvd.init()
    hvd.shutdown()
    assert seen == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    hvd.init()
    hvd.shutdown()
    assert seen == [("jax_compilation_cache_dir", str(tmp_path / "h"))]


def test_launcher_gives_each_slot_of_a_host_its_own_chip():
    from horovod_tpu.runner.hosts import get_host_assignments, parse_hosts

    envs = [s.to_env() for s in
            get_host_assignments(parse_hosts("localhost:4"), 4)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 4
    for e in envs:
        assert e["TPU_PROCESS_BOUNDS"] == "2,2,1"
        assert e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert e["TPU_PROCESS_ADDRESSES"].count(",") == 3
        assert f"localhost:{e['TPU_PROCESS_PORT']}" in \
            e["TPU_PROCESS_ADDRESSES"].split(",")
        assert e["CLOUD_TPU_TASK_ID"] == e["HOROVOD_LOCAL_RANK"]
    # one worker on a host owns the whole host; layouts the recipe does
    # not cover get no assignment (a TPU worker then fails hvd.init())
    for spec, n in (("localhost:1", 1), ("localhost:3", 3),
                    ("a:4,b:4", 8)):
        for s in get_host_assignments(parse_hosts(spec), n):
            assert "TPU_VISIBLE_CHIPS" not in s.to_env()


def test_worker_that_sees_several_chips_is_refused(monkeypatch):
    """hvd.init() under a multi-slot launch on a TPU with more than one
    local chip is a failure, not a warning."""
    from horovod_tpu.common.config import Config
    from horovod_tpu.common.exceptions import HorovodTpuError
    from horovod_tpu.core import topology

    class Chip:
        platform = "tpu"

    cfg = Config(rank=1, size=4, local_rank=1, local_size=4)
    monkeypatch.setattr(jax, "local_devices", lambda: [Chip()] * 4)
    with pytest.raises(HorovodTpuError, match="4 local TPU chips"):
        topology._check_one_chip_per_process(cfg)
    monkeypatch.setattr(jax, "local_devices", lambda: [Chip()])
    topology._check_one_chip_per_process(cfg)  # its own chip: fine
    # a single worker per host owns every chip of the host
    monkeypatch.setattr(jax, "local_devices", lambda: [Chip()] * 4)
    topology._check_one_chip_per_process(Config(rank=0, size=1,
                                                local_size=1))


def test_native_library_is_never_loaded_stale(monkeypatch):
    """When make cannot vouch for the library, a pre-existing .so is not
    loaded: the Python fallbacks run and status() says "absent"."""
    from horovod_tpu import native

    assert native.status() in ("built", "up to date")  # toolchain is here
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_build_failed", False)
    monkeypatch.setattr(native, "_build", lambda: False)
    assert os.path.exists(native._LIB_PATH)
    assert native.load() is None
    assert native.status() == "absent"


def test_cpu_emulation_never_replaces_an_accelerator(monkeypatch):
    from horovod_tpu.common.exceptions import HorovodTpuError
    from horovod_tpu.core import topology

    class Chip:
        platform = "tpu"

    monkeypatch.setattr(jax, "devices", lambda: [Chip()])
    with pytest.raises(HorovodTpuError, match="JAX_PLATFORMS=cpu"):
        topology._apply_cpu_emulation(8)


def test_mesh_position_follows_the_launchers_rank(monkeypatch):
    """On a TPU a device's process_index is its process's place in the
    slice topology, not the launcher's rank (seen on the 2x2 host: ranks
    0,1,2,3 are processes 0,2,3,1). The mesh is ordered by the rank, so
    that rank r's tensor is row r of every rank-addressed collective."""
    from jax.experimental import multihost_utils

    from horovod_tpu.common.config import Config
    from horovod_tpu.core import topology

    class Chip:
        platform = "tpu"

        def __init__(self, id, process_index):
            self.id, self.process_index = id, process_index

    process_of_rank = [0, 2, 3, 1]
    chips = [Chip(i, i) for i in range(4)]
    monkeypatch.setattr(jax, "devices", lambda: chips)
    monkeypatch.setattr(jax, "process_count", lambda: 4)
    monkeypatch.setattr(jax, "process_index", lambda: 2)
    monkeypatch.setattr(
        multihost_utils, "process_allgather",
        lambda x: [(p, r) for r, p in enumerate(process_of_rank)])
    devs = topology._canonical_devices(Config(rank=1, size=4))
    assert [d.process_index for d in devs] == process_of_rank
    # no launcher identity (single controller): topology order, no exchange
    monkeypatch.setattr(multihost_utils, "process_allgather", None)
    devs = topology._canonical_devices(Config())
    assert [d.process_index for d in devs] == [0, 1, 2, 3]
