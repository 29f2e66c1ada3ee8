"""benchmark/trace_reduce.py on a hand-built trace with known answers.

The trace, in microseconds on the profiler's clock (one chip):

    host    bench/window   0 ................................. 20
            bench/submit                        11.5-12.5
    XLA Ops while.3         1 ............. 11
              paged_decode.9  2-4   (Pallas, in the scan: decode kernel)
              copy.12             5 - 8
            fusion.7                              13 - 15
    Async   all-gather-start.1        7 ............. 14
"""

import pytest
from jax.profiler import ProfileData

from benchmark import trace_reduce as tr

TRACE = '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 4000000 duration_ps: 3000000 }
    events { metadata_id: 4 offset_ps: 12000000 duration_ps: 2000000 }
  }
  lines { id: 2 name: "Async XLA Ops" timestamp_ns: 1000
    events { metadata_id: 5 offset_ps: 6000000 duration_ps: 7000000 }
    events { metadata_id: 6 offset_ps: 0 duration_ps: 19000000 }
  }
  lines { id: 3 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 7 offset_ps: 0 duration_ps: 19000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%while.3 = (s32[]{:T(128)}, bf16[4]{0:T(8,128)(2,1)}) while((s32[]{:T(128)}, bf16[4]{0}) %tuple.1), condition=%c, body=%b" } }
  event_metadata { key: 2 value { id: 2 name: "%paged_decode.9 = bf16[4]{0:T(8,128)(2,1)S(1)} custom-call(bf16[4]{0} %x), custom_call_target=\\"tpu_custom_call\\", frontend_attributes={kernel_metadata={}}" } }
  event_metadata { key: 3 value { id: 3 name: "%copy.12 = bf16[4]{0:T(8,128)(2,1)} copy(bf16[4]{0} %x)" } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.7 = bf16[4]{0} fusion(bf16[4]{0} %x), kind=kLoop, calls=%f" } }
  event_metadata { key: 5 value { id: 5 name: "%all-gather-start.1 = (f32[4]{0}, f32[16]{0}) all-gather-start(f32[4]{0} %p), dimensions={0}" } }
  event_metadata { key: 6 value { id: 6 name: "%copy-start.2 = (f32[4]{0}, f32[4]{0}, u32[]) copy-start(f32[4]{0} %p)" } }
  event_metadata { key: 7 value { id: 7 name: "jit_step(123)" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 7 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 11500000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 0 duration_ps: 20000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench/window" } }
  event_metadata { key: 2 value { id: 2 name: "bench/submit" } }
  event_metadata { key: 3 value { id: 3 name: "$profiler.py:101 start_trace" } }
}
'''


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce_trace(ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(TRACE)))


def test_busy_idle_and_window(reduced):
    assert reduced["window_from"] == "bench/window"
    assert reduced["window_s"] == pytest.approx(20e-6)
    # [1, 11] and [13, 15]; a copy in flight on the async line is not busy
    assert reduced["busy_s"] == pytest.approx(12e-6)
    assert reduced["idle_pct"] == pytest.approx(40.0)


def test_self_time_by_name(reduced):
    assert reduced["op_s"] == pytest.approx({
        "while": 5e-6,                       # 10 - (2 + 3)
        "paged_decode (custom-call)": 2e-6,
        "copy": 3e-6, "fusion": 2e-6})
    assert reduced["device_ops"][0] == ["while", pytest.approx(5e-6)]


def test_kernels_are_told_by_their_names(reduced):
    assert reduced["classes"] == pytest.approx(
        {"pallas": 2e-6, "decode_attn": 2e-6})


@pytest.mark.parametrize("label, classes", [
    ("while/paged_decode custom-call tpu_custom_call",
     {"pallas", "decode_attn"}),
    ("while/decode_attn_q8 custom-call tpu_custom_call",
     {"pallas", "decode_attn"}),
    # the frontier write runs in the same scan and is no decode kernel
    ("while/kv_append custom-call tpu_custom_call", {"pallas"}),
    ("conditional/prefill_attn custom-call tpu_custom_call", {"pallas"}),
    # a Pallas call without a name of its own is in no class but pallas
    ("while/closed_call custom-call tpu_custom_call", {"pallas"}),
    ("closed_call custom-call tpu_custom_call", {"pallas"}),
    ("flash_fwd custom-call tpu_custom_call", {"pallas", "flash"}),
    ("call/flash_bwd_fused custom-call tpu_custom_call",
     {"pallas", "flash"}),
    ("flash_fwd/fusion fusion", set()),
    ("while/fusion fusion", set()),
])
def test_a_class_is_told_by_the_kernels_name_alone(label, classes):
    table = tr.kernel_names()["classes"]
    assert {c for c in ("pallas", "flash", "decode_attn")
            if tr._matches(table[c], label)} == classes


def test_collective_time_and_the_part_nothing_hides(reduced):
    # all-gather in flight 7-14; the copy hides 7-8, the fusion 13-14; the
    # while's own time hides nothing (control flow), nor does the copy-start
    assert reduced["collective_s"] == pytest.approx(7e-6)
    assert reduced["collective_exposed_s"] == pytest.approx(5e-6)


def test_idle_gaps_go_to_the_host_span_that_covers_them(reduced):
    gaps = dict((k, pytest.approx(v)) for k, v in reduced["idle_gaps"])
    # 0-1 and 15-20 under no span but the window; 11-13 half under submit
    assert gaps == {"(no span)": 6e-6, "bench/submit": 2e-6}
    assert "$profiler.py:101 start_trace" not in reduced["spans"]


def test_a_trace_with_no_device_operation_reduces_to_nothing():
    empty = TRACE.replace("/device:TPU:0", "/device:CUSTOM:other")
    assert tr.reduce_trace(ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(empty))) is None


@pytest.mark.parametrize("name, parsed", [
    ("%fusion.4443 = bf16[8,2048]{1,0:T(8,128)(2,1)} fusion(bf16[8]{0} %a)",
     ("fusion", "fusion", "")),
    ("%convolution_add_fusion.74 = f32[5,4]{1,0:T(8,128)} fusion(f32[5] %a)",
     ("convolution_add_fusion", "fusion", "")),
    ("%all-reduce-start.12 = f32[4]{0} all-reduce-start(f32[4]{0} %g)",
     ("all-reduce-start", "all-reduce-start", "")),
    ("fusion.12", ("fusion", "fusion", "")),
])
def test_names_are_cut_from_hlo_text(name, parsed):
    assert tr.parse_name(name) == parsed


def test_interval_arithmetic():
    assert tr.union([(5, 7), (1, 3), (2, 4), (9, 9)]) == [(1, 4), (5, 7)]
    assert tr.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == \
        [(0, 1), (2, 4), (6, 9)]
    assert tr.total([(1, 4), (5, 7)]) == 5
