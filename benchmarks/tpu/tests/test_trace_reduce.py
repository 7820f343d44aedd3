"""Trace-to-metrics reduction, on a hand-built trace and on a small trace
recorded on one TPU v5e (``data/v5e_paged_decode.xplane.pb``: three calls
of a jitted step that runs the ``paged_decode`` kernel and a matmul, each
inside a ``bench.step`` span, with a 10 ms ``bench.submit`` sleep after
each, all inside ``bench.window``)."""

import os

import pytest

import trace_reduce as T

DATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "data")


def test_union_counts_overlap_once():
    assert T.union([(0, 10), (5, 15), (20, 30), (30, 31), (40, 40)]) == [
        (0, 15), (20, 31)]
    assert T.union([(5, 6), (0, 10)]) == [(0, 10)]
    assert T.clip([(0, 15), (20, 31)], 10, 25) == [(10, 15), (20, 25)]


# Host spans on one plane; device ops on another.  Window 1000..11000 ns.
# Device busy: [1000,3000)+[2000,5000) = [1000,5000); [6000,7000) nested
# in [6000,8000); [10500,12000) clipped to 11000.  Idle: [5000,6000)
# under bench.step, [8000,10500) under bench.submit.
SYNTH = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 3000000 }
    events { metadata_id: 1 offset_ps: 6000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 6000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 10500000 duration_ps: 1500000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 0 duration_ps: 99000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = bf16[4] fusion(bf16[4] %a)" } }
  event_metadata { key: 2 value { id: 2 name: "%_paged_decode_jit.7 = (f32[4]) custom-call(bf16[4] %q), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 3 value { id: 3 name: "%copy.2 = f32[4] copy(f32[4] %_paged_decode_jit.7)" } }
  event_metadata { key: 4 value { id: 4 name: "jit_step" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 4000000 duration_ps: 3000000 }
    events { metadata_id: 3 offset_ps: 7500000 duration_ps: 3500000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.step" } }
  event_metadata { key: 3 value { id: 3 name: "bench.submit" } }
}
"""


@pytest.fixture
def synth():
    from jax.profiler import ProfileData

    return T.from_profile(ProfileData.from_text_proto(SYNTH))


def test_busy_is_the_union_inside_the_window(synth):
    assert synth.window == (1000, 11000)
    assert synth.devices == ["/device:TPU:0"]
    assert synth.busy("/device:TPU:0") == [(1000, 5000), (6000, 8000),
                                           (10500, 11000)]
    assert synth.busy_s() == pytest.approx(6500e-9)
    assert synth.idle_share() == pytest.approx(1 - 6500 / 10000)


def test_idle_gaps_are_named_by_the_host_span(synth):
    assert synth.idle_gaps() == [("bench.submit", pytest.approx(2500e-9)),
                                 ("bench.step", pytest.approx(1000e-9))]


def test_kernel_found_by_instruction_name(synth):
    secs, n = synth.kernel("paged_decode")
    assert n == 1 and secs == pytest.approx(3000e-9)
    assert synth.kernel("flash")[1] == 0
    ops = dict(synth.op_seconds())
    assert ops["fusion.1"] == pytest.approx((2000 + 2000 + 500) * 1e-9)
    b = synth.breakdown()
    assert b["device_ops"][0][0] == "fusion.1"
    assert b["idle_gaps"][0][0] == "bench.submit"


def test_no_window_is_an_error():
    from jax.profiler import ProfileData

    with pytest.raises(ValueError):
        T.from_profile(ProfileData.from_text_proto(
            SYNTH.replace('"bench.window"', '"other"')))


@pytest.fixture(scope="module")
def recorded():
    return T.load(os.path.join(DATA, "v5e_paged_decode.xplane.pb"))


def test_recorded_window_and_spans(recorded):
    names = [n for n, _, _ in recorded.spans]
    assert names.count("bench.window") == 1
    assert names.count("bench.step") == 3 and names.count("bench.submit") == 3
    assert recorded.devices == ["/device:TPU:0"]
    assert 0.03 < recorded.window_s < 0.05


def test_recorded_mosaic_kernel_found_by_name(recorded):
    secs, n = recorded.kernel("paged_decode")
    assert n == 3 and 0 < secs < recorded.busy_s()
    kernels = [o for o in recorded.ops["/device:TPU:0"] if o.mosaic]
    assert {o.name.split(".")[0] for o in kernels} == {"_paged_decode_jit"}
    # on the host's clock, every kernel run lies inside a bench.step span
    steps = [(s, e) for n, s, e in recorded.spans if n == "bench.step"]
    for o in kernels:
        assert any(s <= o.start and o.end <= e for s, e in steps), o
    assert recorded.breakdown()["device_ops"][0][0].startswith(
        "_paged_decode_jit")


def test_recorded_idle_is_the_host_sleep(recorded):
    busy = recorded.busy("/device:TPU:0")
    assert busy == T.union(busy)
    assert recorded.idle_share() > 0.9
    gaps = dict(recorded.idle_gaps())
    assert max(gaps, key=gaps.get) == "bench.submit"
    assert gaps["bench.submit"] > 0.03
