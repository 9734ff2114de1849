"""The reduction from a profiler trace to what the per-layer metrics
read, on a small hand-made trace whose answers are worked out below,
and on a small trace recorded on a TPU v5e."""
import gzip
import pathlib

import pytest
from jax.profiler import ProfileData
import tracing

DATA = pathlib.Path(__file__).with_name("data")


def _ev(meta, start_ns, dur_ns):
    return (f"events {{ metadata_id: {meta} offset_ps: {start_ns * 1000} "
            f"duration_ps: {dur_ns * 1000} }}")


# device 0: decode 0-10us, prefill 20-25us, decode 40-50us; ops inside
# overlap (0-6, 4-10 -> one busy span 0-10).  The host is in
# `admission` from 10 to 20us and `sampling` from 26 to 39us.
TEXT = f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0
    {_ev(1, 0, 10000)} {_ev(2, 20000, 5000)} {_ev(1, 40000, 10000)} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0
    {_ev(3, 0, 6000)} {_ev(4, 4000, 6000)} {_ev(5, 20000, 5000)}
    {_ev(3, 40000, 10000)} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "jit_fused_decode(7)" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "jit_prefill_admit(9)" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "fusion.1" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "copy.2" }} }}
  event_metadata {{ key: 5 value {{ id: 5 name: "convolution.3" }} }}
}}
planes {{ id: 2 name: "/device:TPU:1"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {_ev(1, 0, 99000)} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "other" }} }}
}}
planes {{ id: 3 name: "/host:CPU"
  lines {{ id: 1 name: "pump" timestamp_ns: 0
    {_ev(1, 10000, 10000)} {_ev(2, 26000, 13000)} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "admission" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "sampling" }} }}
}}
"""


def _data():
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(TEXT))


def test_hand_made_trace():
    s = tracing.reduce(_data(), window_s=60e-6, devices=[0])
    # busy: 0-10, 20-25, 40-50 us on device 0 only (device 1 not read)
    assert s.busy_by_device == {0: pytest.approx(25e-6)}
    assert s.busy_s == pytest.approx(25e-6)
    assert s.program("fused_decode") == (pytest.approx(20e-6), 2)
    assert s.program("prefill_admit") == (pytest.approx(5e-6), 1)
    assert s.program("spec_decode") == (0.0, 0)
    assert s.device_ops[0] == ("fusion.1", pytest.approx(16e-6))
    # gaps 25-40 (15us, host sampling) and 10-20 (10us, host admission)
    assert s.idle_gaps == [("sampling", pytest.approx(15e-6)),
                           ("admission", pytest.approx(10e-6))]


def test_busy_is_averaged_over_the_chips_read():
    s = tracing.reduce(_data(), window_s=1e-4, devices=[0, 1])
    assert s.busy_s == pytest.approx((25e-6 + 99e-6) / 2)


def test_merge():
    assert tracing.merge([(5, 7), (0, 2), (1, 3), (6, 9)]) == \
        [(0, 3), (5, 9)]


def test_recorded_tpu_trace():
    """Three rounds of (host work annotated `bench.host_work`, a bf16
    1024x1024 matmul-and-sum, a tanh) traced on one TPU v5e chip."""
    raw = gzip.decompress((DATA / "v5e_small.xplane.pb.gz").read_bytes())
    s = tracing.reduce(ProfileData.from_serialized_xspace(raw),
                       window_s=1.0, devices=[0])
    # both jitted lambdas are named jit__lambda: six runs in all, and
    # the union of their ops lies inside the programs' own spans
    assert list(s.programs) == ["jit__lambda"]
    secs, runs = s.programs["jit__lambda"]
    assert runs == 6
    assert s.busy_s == pytest.approx(77.451e-6)
    assert s.busy_s <= secs
    assert s.device_ops[0][0].startswith("%convolution_reduce_fusion")
    assert [name for name, _ in s.idle_gaps[:2]] == ["bench.host_work"] * 2
