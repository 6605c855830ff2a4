"""bench/trace.py on a small hand-made trace: two TPU devices, ops that
start before and end after the traced window, a collective partly hidden
by a kernel, and idle gaps named by what the host was doing."""
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402

US = 1e-6


@pytest.fixture(scope="module")
def red():
    prof = trace.load(str(ROOT / "tests/bench/data/small_trace.pbtxt"))
    return trace.reduce(prof, {"gae": r"jit\(gae\)"})


def test_window_is_the_host_annotation(red):
    assert red["devices"] == 2
    assert red["window_s"] == pytest.approx(100 * US)


def test_busy_is_the_union_of_ops_clipped_to_the_window(red):
    # TPU:0 [10, 40] + [50, 60] = 40 us; TPU:1 [10, 50] + [60, 70] = 50 us
    assert red["busy_s"] == pytest.approx(45 * US)


def test_kernel_time_and_calls(red):
    assert red["kernel_calls"] == {"gae": 2}
    assert red["kernel_s"]["gae"] == pytest.approx(20 * US)


def test_collective_time_not_hidden_by_compute(red):
    # TPU:0 all-reduce [25, 40] less the kernel's [25, 30]: 10 us;
    # TPU:1 all-reduce [30, 50] alone: 20 us
    assert red["collective_s"] == pytest.approx(15 * US)


def test_top_ops_are_per_device_means(red):
    ops = dict(red["device_ops"])
    assert ops["all-reduce.1"] == pytest.approx(17.5 * US)
    assert ops["fusion.1"] == pytest.approx(15 * US)
    assert ops["custom-call.1"] == pytest.approx(10 * US)
    assert ops["fusion.2"] == pytest.approx(5 * US)
    assert "fusion.0" not in ops and "fusion.3" not in ops
    assert [k for k, _ in red["device_ops"]][0] == "all-reduce.1"


def test_idle_gaps_named_by_host_activity(red):
    # TPU:0 idles [40, 50] under the launch and [60, 110] under the fetch
    assert red["idle_gaps"] == [
        ["engine.fetch", pytest.approx(50 * US)],
        ["PjitFunction(launch)", pytest.approx(10 * US)]]


@pytest.mark.parametrize("a,b,want", [
    ([(0, 10)], [(2, 4), (6, 8)], 6),
    ([(0, 10), (20, 30)], [(5, 25)], 10),
    ([(0, 10)], [], 10),
    ([(0, 10)], [(0, 10)], 0),
])
def test_interval_difference(a, b, want):
    assert trace._minus(a, b) == want


def test_trace_without_window_or_device_is_an_error():
    from jax.profiler import ProfileData
    host_only = ProfileData.from_text_proto(
        'planes { id: 1 name: "/host:CPU" lines { id: 1 name: "python" '
        'events { metadata_id: 1 offset_ps: 0 duration_ps: 10 } } '
        'event_metadata { key: 1 value { id: 1 name: "bench.window" } } }')
    with pytest.raises(ValueError, match="no TPU device"):
        trace.reduce(host_only)
    with pytest.raises(ValueError, match="no host annotation"):
        trace.reduce(ProfileData.from_text_proto(
            'planes { id: 1 name: "/host:CPU" }'))


def test_kernel_pattern_matches_the_kernel_and_not_its_readers():
    pat = re.compile(trace.kernel_pattern("gae"))
    kernel = ('%gae.2 = f32[64,4096]{1,0} custom-call(f32[64,4096]{1,0} '
              '%while.56), custom_call_target="tpu_custom_call"')
    reader = ('%add_bitcast_fusion.1 = f32[8,32,8,128]{3,2,1,0} fusion('
              'f32[64,4096]{1,0} %gae.2), kind=kLoop')
    assert pat.search(kernel) and not pat.search(reader)
    assert not re.search(trace.kernel_pattern("gae"), kernel.replace(
        "%gae.2", "%gae_like.2"))
