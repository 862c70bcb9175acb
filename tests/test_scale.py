"""Scale smoke: the eager engine at 32 emulated ranks.

The per-rank Python loops the engine is allowed to keep must stay cheap
as k grows (uneven allgather's slice-concat is O(k) of tiny slices;
alltoall's chunk extraction is one gather — O(1) program size after the
round-2 rework). A subprocess owns its own 32-device virtual platform
(the session conftest pins 8)."""

import os
import subprocess
import sys
import textwrap

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=32"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import horovod_tpu as hvd

    hvd.init()
    k = hvd.size()
    assert k == 32, k

    # allreduce
    x = np.arange(k * 4, dtype=np.float32).reshape(k, 4)
    out = np.asarray(hvd.allreduce(x, op="sum"))
    np.testing.assert_allclose(out[0], x.sum(axis=0), rtol=1e-5)

    # allgather: this-rank (2, 3) replicated to every slot -> 64 rows
    g = np.asarray(hvd.allgather(np.ones((2, 3), np.float32)))
    assert g.shape == (k * 2, 3), g.shape

    # alltoall: stacked (k, 2k, 1) — 2 rows to each destination. The
    # single gather-based chunk extraction keeps the program O(1) in k.
    a2a_in = np.tile(np.arange(2 * k, dtype=np.float32).reshape(2 * k, 1),
                     (k, 1, 1))
    results = hvd.alltoall(a2a_in)
    assert isinstance(results, list) and len(results) == k
    out0, splits0 = results[0]
    assert np.asarray(out0).shape == (2 * k, 1)
    np.testing.assert_array_equal(np.asarray(splits0), np.full(k, 2))

    # grouped allreduce of a 40-tensor gradient set through fusion
    ts = [np.full((k, 8), float(i), np.float32) for i in range(40)]
    outs = hvd.grouped_allreduce(ts, op="sum")
    np.testing.assert_allclose(np.asarray(outs[7])[0], 7.0 * k, rtol=1e-5)

    hvd.barrier()
    print("SCALE32_OK")
""")


def test_scale_32_ranks(tmp_path):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["HOROVOD_TPU_EMULATE_RANKS"] = "32"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True,
                         timeout=240)   # under `TEST_LIMIT_S` (conftest.py)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "SCALE32_OK" in out.stdout
