"""The trace reduction on the small recorded trace (``data/``; recorded on a
TPU v5 lite by ``record_trace.py``: inside the ``bench.trace_window``
annotation, three fenced calls of the fused interaction kernel and of one
1024^2 bf16 matmul, 20 ms of host sleep before, between and after)."""

import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

pytest.importorskip("jax")
from benchmark.harness import costs, layers, peaks, xplane  # noqa: E402

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "small_trace.xplane.pb")
KERNEL = r"= f32\[\d+,351\]\S* custom-call\(.*tpu_custom_call"


@pytest.fixture(scope="module")
def summary():
    return xplane.reduce_trace(TRACE)


def test_window_is_the_annotation_and_busy_is_far_below_it(summary):
    assert summary.devices == 1
    # four sleeps of 20 ms and six fenced calls
    assert 0.08 < summary.window_s < 0.5
    assert 0 < summary.busy_s < 0.005
    # nothing overlaps in this trace: the union is the sum
    assert summary.busy_s == pytest.approx(
        sum(s for _, s in summary.ops.values()), rel=1e-9)


def test_every_call_of_the_kernel_and_the_matmul_is_found(summary):
    kernel = [(n, s) for name, (n, s) in summary.ops.items()
              if re.search(KERNEL, name)]
    assert len(kernel) == 1 and kernel[0][0] == 3
    # a bytes-bound kernel of 6.4 MB cannot run faster than 7.8 us a call
    assert kernel[0][1] / 3 > 7.8e-6
    matmul = [n for name, (n, _) in summary.ops.items()
              if "bf16[1024,1024]" in name and " fusion(" in name]
    assert matmul == [3]
    assert not any(name.startswith(xplane.CONTAINERS) for name in summary.ops)


def test_idle_goes_to_what_the_host_was_doing(summary):
    names = [name for name, _ in summary.idle_gaps]
    assert "sleep" in names[0]
    idle = sum(s for _, s in summary.idle_gaps)
    assert idle == pytest.approx(summary.window_s - summary.busy_s, rel=0.02)
    assert len(summary.device_ops) <= 10 and len(summary.idle_gaps) <= 10
    assert all(len(name) <= 96 for name, _ in summary.device_ops)


def test_the_roofline_reader_on_the_recorded_kernel(summary, tmp_path):
    spec = tmp_path / "k.json"
    spec.write_text('{"reader": "trace_kernel_roofline", "op_pattern": %s, '
                    '"cost": "dot_interaction"}' % __import__("json").dumps(KERNEL))
    sources = {"trace": summary, "peaks": peaks.peaks_for("TPU v5 lite"),
               "kernels": {"dot_interaction": {
                   "cost": costs.dot_interaction(2048, 27, 16, 4)}}}
    share = layers.read_metric(str(spec), sources)
    assert 1.0 < share < 100.0


def test_short_names():
    line = ('%fusion.12 = f32[2048,16]{1,0:T(8,128)S(1)} fusion(f32[5,16]{1,0} '
            '%x), kind=kLoop')
    assert xplane.short_name(line) == "fusion.12 f32[2048,16]"
    line = ('%k.1 = f32[8,351]{1,0:T(8,128)} custom-call(f32[8,27,16]{2,1,0} %c)'
            ', custom_call_target="tpu_custom_call"')
    assert xplane.short_name(line) == "k.1 f32[8,351] tpu_custom_call"
    line = '%m.2 = (f32[]{:T(128)}, f32[10,16]{0,1:T(8,128)}) fusion(f32[] %a)'
    assert xplane.short_name(line) == "m.2 (f32[], f32[10,16])"


def test_a_trace_without_a_tpu_plane_is_refused(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    jnp.ones(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    with pytest.raises(ValueError, match="no plane named"):
        xplane.reduce_trace(xplane.find_xplane(str(tmp_path)))
