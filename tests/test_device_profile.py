"""Device-profile aggregation (profiler/device_profile.py) against a
synthetic xplane — the parsing/aggregation must be right without TPU
hardware; the e2e path (jax.profiler → xplane → table) runs on TPU via
scripts/trace_resnet.py."""

import pytest

from horovod_tpu.profiler.device_profile import aggregate_xspace, classify


@pytest.fixture(scope="module", autouse=True)
def _xplane():
    """TensorFlow's xplane protocol as this module's global, imported when
    the first test here runs and not when the file is collected: every
    worker collects every file, one runs this one. Without TensorFlow the
    file's tests are skipped (`tests/test_device_profile_no_tf.py` runs)."""
    globals()["xplane_pb2"] = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")


def _make_xspace():
    xs = xplane_pb2.XSpace()
    plane = xs.planes.add()
    plane.name = "/device:TPU:0"
    plane.event_metadata[1].id = 1
    plane.event_metadata[1].name = "%convolution_fusion.1"
    plane.event_metadata[2].id = 2
    plane.event_metadata[2].name = "%select_and_scatter.9"
    plane.event_metadata[3].id = 3
    plane.event_metadata[3].name = "%copy-done.5"
    line = plane.lines.add()
    line.name = "XLA Ops"
    for mid, dur_ms, n in ((1, 2.0, 3), (2, 0.5, 3), (3, 0.1, 6)):
        for _ in range(n):
            e = line.events.add()
            e.metadata_id = mid
            e.duration_ps = int(dur_ms * 1e9)
    # a host plane that must be ignored
    host = xs.planes.add()
    host.name = "/host:CPU"
    hl = host.lines.add()
    hl.name = "XLA Ops"
    he = hl.events.add()
    he.metadata_id = 1
    he.duration_ps = int(99e9)
    host.event_metadata[1].id = 1
    host.event_metadata[1].name = "host_noise"
    return xs


def test_aggregate_per_op_and_category():
    prof = aggregate_xspace(_make_xspace(), reps=3)
    # per step: conv 2.0, sas 0.5, copies 0.1*6/3 = 0.2
    assert prof.per_op["%convolution_fusion.1"] == pytest.approx(2.0)
    assert prof.per_op["%select_and_scatter.9"] == pytest.approx(0.5)
    assert prof.per_op["%copy-done.5"] == pytest.approx(0.2)
    assert prof.total_ms == pytest.approx(2.7)
    assert prof.per_category["convolution/custom-call"] == pytest.approx(2.0)
    assert prof.per_category["maxpool backward"] == pytest.approx(0.5)
    assert prof.per_category["layout/copy"] == pytest.approx(0.2)
    # host plane excluded
    assert "host_noise" not in prof.per_op


def test_markdown_and_top_ops():
    prof = aggregate_xspace(_make_xspace(), reps=3)
    md = prof.as_markdown(top=2)
    assert "| convolution/custom-call | 2.00 |" in md
    assert md.count("| `%") == 2  # top=2 individual rows
    assert prof.top_ops(1)[0][0] == "%convolution_fusion.1"


def test_classify_buckets():
    assert classify("%multiply_reduce_fusion.4") == \
        "reduce fusion (stats/grads)"
    assert classify("%all-reduce.1") == "collective"
    assert classify("%weird_thing") == "other"
    # fusions NAMED after layout ops are compute, not copies (the
    # unanchored pattern mislabeled half an Inception step in r05)
    assert classify("%dynamic-slice_bitcast_fusion") == \
        "fused elementwise/compute"
    assert classify("%broadcast_maximum_fusion.2") == \
        "fused elementwise/compute"
    assert classify("%copy.563") == "layout/copy"
    assert classify("%copy-done.5") == "layout/copy"
    assert classify("%bitcast.601") == "layout/copy"
    assert classify("%transpose.12") == "layout/copy"
    assert classify("%conv1x1_bn_bwd_fused.1") == "pallas kernel"


@pytest.mark.parametrize("name, category", [
    # a Mosaic kernel is named after the scope it was called under
    ("%attn.attend.35", "pallas kernel"),
    ("mla.attend.61", "pallas kernel"),
    ("%gdn.scan.6", "pallas kernel"),
    ("%moe.experts.12", "pallas kernel"),
    # anchored: a fusion named after its root is not a kernel
    ("%fusion.attn.attend", "fused elementwise/compute"),
    ("%attn.attendant.1", "other"),
    # a differentiated fusion is no kernel for carrying "jvp" in its name
    ("%jvp_multiply_fusion.3", "fused elementwise/compute"),
    ("%transpose_jvp___.48", "other"),
])
def test_classify_tells_kernels_by_their_scopes_names(name, category):
    assert classify(name) == category
    assert classify("%custom-call.62") == "convolution/custom-call"
