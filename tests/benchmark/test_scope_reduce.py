"""benchmark/scope_reduce.py on a hand-built trace with known answers, and
each reader over it on the tiny cells, traced on the CPU.

The hand-built trace, in microseconds on the profiler's clock. One chip, one
program ``jit_mixed_step(77)`` whose HLO (op_names below) is embedded in the
``/host:metadata`` plane, as the profiler embeds it:

    XLA Ops  while.1            1 ........................ 21   decode_scan/while
               paged_decode.3     2-4, 5-7   (two calls)   decode_scan/../attn/paged_decode
               copy.5                 8 - 12               (no op_name: compiler-inserted)
               slice_bitcast_fusion.2    12 - 15           decode_scan/../kv_write
               fusion.9                      15 - 18       decode_scan/../mlp
             conditional.2                       22 .... 30  prefill_lane/cond
               prefill_attn.1                      23-26     prefill_lane/../attn/prefill_attn
             copy.7                                   31-33  (no op_name, no parent)
    host     inference/step   0 ................................ 40
               schedule 0-5, mixed_step 5-7, harvest 7-36, deliver 36-39
             request 7: submitted 1, admitted 3, first_token 37
             request 8: admitted 3.5 carrying queue_ms=12.5 (submitted before)
             request 9: a chunk at 38 carrying queue_ms=4, prefill_ms=900

A second "device" is a CPU-style plane: an interpreted kernel whose
operations carry the kernel's name in their op_name, run twice.
"""

import pytest
from jax.profiler import ProfileData

from benchmark import scope_reduce as sr
from benchmark import harness
from tests.benchmark import tiny


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, payload):
    """One length-delimited field of a protobuf message."""
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(number << 3 | 2) + _varint(len(payload)) + payload


def _hlo(module, instructions):
    """A serialized ``HloProto`` holding ``{instruction: (opcode,
    op_name)}`` in one computation."""
    body = b"".join(
        _field(2, _field(1, name) + _field(2, opcode)
               + (_field(7, _field(2, op_name)) if op_name else b""))
        for name, (opcode, op_name) in instructions.items())
    return _field(1, _field(1, module) + _field(3, _field(1, "main") + body))


def _escaped(blob):
    return "".join("\\{:03o}".format(b) for b in blob)


MIXED = {
    "while.1": ("while", "jit(mixed_step)/decode_scan/while"),
    "paged_decode.3": (
        "custom-call", "jit(mixed_step)/decode_scan/while/body/closed_call/"
        "attn/paged_decode/pallas_call"),
    "copy.5": ("copy", ""),
    "slice_bitcast_fusion.2": (
        "fusion", "jit(mixed_step)/decode_scan/while/body/closed_call/"
        "kv_write/dynamic_update_slice"),
    "fusion.9": ("fusion", "jit(mixed_step)/decode_scan/while/body/"
                 "closed_call/mlp/dot_general"),
    "conditional.2": ("conditional", "jit(mixed_step)/prefill_lane/cond"),
    "prefill_attn.1": (
        "custom-call", "jit(mixed_step)/prefill_lane/cond/branch_1_fun/attn/"
        "prefill_attn/pallas_call"),
    "copy.7": ("copy", ""),
}
TRAIN = {
    "while.4": ("while", "jit(train_step)/jvp(GPT2LMHeadModel)/h_0/block/"
                "attn/attn/flash_fwd/flash_fwd/while"),
    "add.1": ("add", "jit(train_step)/jvp(GPT2LMHeadModel)/h_0/block/attn/"
              "attn/flash_fwd/flash_fwd/while/body/add"),
    "pad.2": ("pad", "jit(train_step)/jvp(GPT2LMHeadModel)/h_0/block/attn/"
              "attn/flash_fwd/flash_fwd/pad"),
    "dot.6": ("dot", "jit(train_step)/transpose(jvp(GPT2LMHeadModel))/"
              "lm_head/dot_general"),
}


def _event(meta, start_us, length_us, stats=""):
    return "events {{ metadata_id: {} offset_ps: {} duration_ps: {} {} }}\n" \
        .format(meta, int(start_us * 1e6), int(length_us * 1e6), stats)


def _int(stat_id, value):
    return "stats {{ metadata_id: {} int64_value: {} }}".format(stat_id, value)


def trace_text(mixed):
    return '''
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    ''' + _event(20, 0, 40) + '''  }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    ''' + _event(1, 1, 20) + _event(2, 2, 2) + _event(2, 5, 2) \
    + _event(3, 8, 4) + _event(4, 12, 3) + _event(5, 15, 3) \
    + _event(6, 22, 8) + _event(7, 23, 3) + _event(8, 31, 2) + '''  }
  event_metadata { key: 1 value { id: 1 name: "%while.1 = (s32[]) while((s32[]) %t), condition=%c, body=%b" } }
  event_metadata { key: 2 value { id: 2 name: "%paged_decode.3 = bf16[4]{0} custom-call(bf16[4]{0} %x), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 3 value { id: 3 name: "%copy.5 = bf16[4]{0} copy(bf16[4]{0} %x)" } }
  event_metadata { key: 4 value { id: 4 name: "%slice_bitcast_fusion.2 = bf16[4]{0} fusion(bf16[4]{0} %x), kind=kLoop, calls=%f" } }
  event_metadata { key: 5 value { id: 5 name: "%fusion.9 = bf16[4]{0} fusion(bf16[4]{0} %x), kind=kOutput, calls=%g" } }
  event_metadata { key: 6 value { id: 6 name: "%conditional.2 = bf16[4]{0} conditional(pred[] %p, bf16[4]{0} %x), true_computation=%a, false_computation=%b" } }
  event_metadata { key: 7 value { id: 7 name: "%prefill_attn.1 = bf16[4]{0} custom-call(bf16[4]{0} %x), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 8 value { id: 8 name: "%copy.7 = bf16[4]{0} copy(bf16[4]{0} %x)" } }
  event_metadata { key: 20 value { id: 20 name: "jit_mixed_step(77)" } }
}
planes { id: 2 name: "/host:metadata"
  event_metadata { key: 77 value { id: 77 name: "jit_mixed_step(77)"
    stats { metadata_id: 1 bytes_value: "''' + _escaped(_hlo("jit_mixed_step", mixed)) + '''" } } }
  event_metadata { key: 5 value { id: 5 name: "jit_train_step(5)"
    stats { metadata_id: 1 bytes_value: "''' + _escaped(_hlo("jit_train_step", TRAIN)) + '''" } } }
  stat_metadata { key: 1 value { id: 1 name: "Hlo Proto" } }
}
planes { id: 3 name: "/host:CPU"
  lines { id: 7 name: "python" timestamp_ns: 0
    ''' + _event(1, 0, 40, _int(1, 1)) + _event(2, 0, 5, _int(1, 1)) \
    + _event(3, 5, 2, _int(1, 1)) + _event(4, 7, 29, _int(1, 1)) \
    + _event(5, 36, 3, _int(1, 1)) \
    + _event(6, 1, 0, _int(2, 7)) + _event(7, 3, 0, _int(2, 7)) \
    + _event(8, 37, 0, _int(2, 7)) \
    + _event(7, 3.5, 0, _int(2, 8)
             + " stats { metadata_id: 3 double_value: 12.5 }") \
    + _event(9, 38, 0, _int(2, 9)
             + " stats { metadata_id: 3 double_value: 4.0 }"
             + " stats { metadata_id: 4 double_value: 900.0 }") \
    + _event(10, 0, 40) + '''  }
  lines { id: 8 name: "tf_XLAEigen/1" timestamp_ns: 0
    ''' + "".join(
        _event(11, start, 6, 'stats { metadata_id: 5 str_value: "while.4" } '
               + _int(6, 5))
        + "".join(_event(12, start + 1 + k, 1,
                         'stats { metadata_id: 5 str_value: "add.1" } '
                         + _int(6, 5)) for k in range(3))
        + _event(13, start + 6, 1,
                 'stats { metadata_id: 5 str_value: "pad.2" } ' + _int(6, 5))
        for start in (50, 60)) \
    + _event(14, 70, 5, 'stats { metadata_id: 5 str_value: "dot.6" } '
             + _int(6, 5)) + '''  }
  event_metadata { key: 1 value { id: 1 name: "inference/step" } }
  event_metadata { key: 2 value { id: 2 name: "inference/schedule" } }
  event_metadata { key: 3 value { id: 3 name: "inference/mixed_step" } }
  event_metadata { key: 4 value { id: 4 name: "inference/harvest" } }
  event_metadata { key: 5 value { id: 5 name: "inference/deliver" } }
  event_metadata { key: 6 value { id: 6 name: "request/submitted" } }
  event_metadata { key: 7 value { id: 7 name: "request/admitted" } }
  event_metadata { key: 8 value { id: 8 name: "request/first_token" } }
  event_metadata { key: 9 value { id: 9 name: "request/chunk" } }
  event_metadata { key: 10 value { id: 10 name: "bench/window" } }
  event_metadata { key: 11 value { id: 11 name: "while.4" } }
  event_metadata { key: 12 value { id: 12 name: "add.1" } }
  event_metadata { key: 13 value { id: 13 name: "pad.2" } }
  event_metadata { key: 14 value { id: 14 name: "dot.6" } }
  stat_metadata { key: 1 value { id: 1 name: "step" } }
  stat_metadata { key: 2 value { id: 2 name: "rid" } }
  stat_metadata { key: 3 value { id: 3 name: "queue_ms" } }
  stat_metadata { key: 4 value { id: 4 name: "prefill_ms" } }
  stat_metadata { key: 5 value { id: 5 name: "hlo_op" } }
  stat_metadata { key: 6 value { id: 6 name: "program_id" } }
}
'''


TRACE = trace_text(MIXED)


@pytest.fixture(scope="module")
def trace_file(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "hand.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(TRACE))
    return str(path)


@pytest.fixture(scope="module")
def chip(trace_file):
    return sr.reduce_scopes(trace_file, ["/device:TPU:0"])


@pytest.fixture(scope="module")
def cpu(trace_file):
    return sr.reduce_scopes(trace_file, ["/host:CPU"])


US = 1e-6


def test_the_embedded_hlo_is_read_from_the_wire_format(trace_file):
    programs = sr.embedded_hlo(trace_file)
    assert set(programs) == {77, 5}
    assert programs[77] == ("jit_mixed_step", MIXED)
    assert programs[5] == ("jit_train_step", TRAIN)


def test_self_time_by_region_with_a_scan_and_an_unscoped_copy(chip):
    assert chip["scope_s"] == pytest.approx({
        "decode_scan": 10 * US,           # the while's own 6 + the copy's 4
        "decode_scan/attn": 4 * US,
        "decode_scan/kv_write": 3 * US,
        "decode_scan/mlp": 3 * US,
        "prefill_lane": 5 * US,           # the conditional's own
        "prefill_lane/attn": 3 * US,
        sr.UNSCOPED: 2 * US})
    # the compiler's copy took its region from the while it nests in
    assert chip["inherited_s"] == pytest.approx({"decode_scan": 4 * US})
    assert chip["named_s"] == pytest.approx(28 * US)
    # the words the embedded programs hold at all, whether they ran or not
    assert chip["regions"] == ["attn", "block", "decode_scan", "kv_write",
                               "lm_head", "mlp", "prefill_lane"]
    assert chip["scope_ops"]["decode_scan"] == pytest.approx(
        {"while": 6 * US, "copy": 4 * US})
    assert chip["scope_ops"][sr.UNSCOPED] == pytest.approx({"copy": 2 * US})


def test_kernels_by_name_with_their_calls(chip):
    assert chip["kernels"] == {
        "paged_decode": {"s": pytest.approx(4 * US), "calls": 2},
        "prefill_attn": {"s": pytest.approx(3 * US), "calls": 1}}
    assert sr.kernel_total(chip["kernels"], "paged_decode") == \
        (pytest.approx(4 * US), 2)
    assert sr.kernel_total(chip["kernels"], "flash_") == (0, 0)


def test_pure_data_movement_inside_the_scan(chip):
    # copy.5 (by nesting under the scan) and slice_bitcast_fusion.2; not
    # fusion.9 (it may compute), not copy.7 (outside the scan)
    assert chip["move_scan_s"] == pytest.approx(7 * US)
    # copy.7, where the step leaves: what step_move_time_pct reads
    assert chip["move_outside_s"] == pytest.approx(2 * US)
    assert sr.under(chip["scope_s"], "decode_scan") == pytest.approx(20 * US)
    assert sr.under(chip["scope_s"], "prefill_lane") == pytest.approx(8 * US)
    assert sr.under(chip["scope_s"], "lm_head") == 0


def test_an_interpreted_kernel_is_found_by_its_scope_and_counted(cpu):
    # two runs of while.4 (3 add.1 inside each) and pad.2: two calls
    assert cpu["kernels"] == {
        "flash_fwd": {"s": pytest.approx(14 * US), "calls": 2}}
    assert cpu["scope_s"] == pytest.approx(
        {"block/attn": 14 * US, "lm_head": 5 * US})
    assert cpu["move_scan_s"] == 0 and cpu["move_outside_s"] == 0


def test_host_spans_give_self_time(chip):
    host = chip["host"]
    assert set(host) == {"inference/step", "inference/schedule",
                         "inference/mixed_step", "inference/harvest",
                         "inference/deliver"}
    assert host["inference/step"] == {
        "count": 1, "total_s": pytest.approx(40 * US),
        "self_s": pytest.approx(1 * US)}
    assert host["inference/harvest"]["self_s"] == pytest.approx(29 * US)
    assert sr.host_ms_a_step(
        host, ("inference/step", "inference/schedule", "inference/mixed_step",
               "inference/deliver"), "inference/step") == \
        pytest.approx(11e-3)
    assert sr.host_ms_a_step(host, ("train/step",), "train/step") is None


def test_request_instants_pair_by_rid(chip):
    instants = chip["instants"]
    assert [(r, t) for r, t, _ in instants["request/admitted"]] == \
        [(7, pytest.approx(3 * US)), (8, pytest.approx(3.5 * US))]
    # 7 on the profiler's clock; 8 and 9 admitted before the trace opened:
    # what their instants carry
    assert sr.request_gaps_ms(instants, "request/submitted",
                              "request/admitted", "queue_ms") == \
        pytest.approx([2e-3, 12.5, 4.0])
    # 8 has no first token yet: no sample; 9's fell before the trace
    assert sr.request_gaps_ms(instants, "request/admitted",
                              "request/first_token", "prefill_ms") == \
        pytest.approx([34e-3, 900.0])
    assert sr.request_gaps_ms({}, "request/admitted", "request/first_token",
                              "prefill_ms") == []


def test_a_trace_without_names_reduces_to_unscoped(trace_file, tmp_path):
    """What the parent commit's program gives: no op_name holds a region,
    no kernel has a name, no engine span. Nothing raises."""
    renamed = {"paged_decode": "closed_call", "prefill_attn": "branch_1_fun",
               "inference/": "engine_", "request/": "req_"}
    bare = trace_text({
        name.replace("paged_decode", "closed_call").replace(
            "prefill_attn", "branch_1_fun"): (opcode, "")
        for name, (opcode, _) in MIXED.items()})
    for old, new in renamed.items():
        bare = bare.replace(old, new)
    path = tmp_path / "bare.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(bare))
    got = sr.reduce_scopes(str(path), ["/device:TPU:0"])
    assert set(got["scope_s"]) == {sr.UNSCOPED}
    assert got["kernels"] == {} and got["host"] == {} and \
        got["instants"] == {} and got["named_s"] == 0
    assert "decode_scan" not in got["regions"]
    # the scan is then told by nesting: copy.5 and slice_bitcast_fusion.2
    assert got["move_scan_s"] == pytest.approx(7 * US)


def test_a_program_the_trace_does_not_embed_is_read_from_the_programs_table(
        tmp_path, monkeypatch, chip):
    """The profiler leaves the four-chip step's HLO out of the file; the
    program's own table of the steps it analysed stands in."""
    from deepspeed_tpu.telemetry import xray

    missing = trace_text(MIXED).replace("key: 77", "key: 78")
    path = tmp_path / "missing.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(missing))
    monkeypatch.setattr(xray, "OP_NAMES", {}, raising=False)
    bare = sr.reduce_scopes(str(path), ["/device:TPU:0"])
    assert set(bare["scope_s"]) == {sr.UNSCOPED}
    monkeypatch.setattr(xray, "OP_NAMES", {"jit_mixed_step": {
        name: op_name for name, (_, op_name) in MIXED.items() if op_name}})
    got = sr.reduce_scopes(str(path), ["/device:TPU:0"])
    assert got["scope_s"] == pytest.approx(chip["scope_s"])
    assert got["kernels"] == chip["kernels"]
    assert got["move_scan_s"] == pytest.approx(chip["move_scan_s"])
    assert "decode_scan" in got["regions"]


@pytest.mark.parametrize("op_name, parts, region", [
    ("jit(train_step)/transpose(jvp(GPT2LMHeadModel))/lm_head/dot_general",
     ["train_step", "GPT2LMHeadModel", "lm_head", "dot_general"], "lm_head"),
    ("jit(train_step)/jvp(GPT2LMHeadModel)/h_3/block/attn/attn/c_attn/dot",
     ["train_step", "GPT2LMHeadModel", "h_3", "block", "attn", "attn",
      "c_attn", "dot"], "block/attn"),
    ("jit(train_step)/optimizer/sqrt", ["train_step", "optimizer", "sqrt"],
     "optimizer"),
    ("jit(f)/mul", ["f", "mul"], ""),
])
def test_regions_are_cut_from_op_names(op_name, parts, region):
    assert sr.components(op_name) == parts
    assert sr.scope_path(parts, set(sr.scope_names()["scopes"])) == region


@pytest.mark.parametrize("parts, instruction, kernel", [
    # off the chip: a scope of the op_name; on it: the instruction's name
    (["mixed_step", "decode_scan", "while", "body", "attn", "paged_decode",
      "pallas_call"], "custom-call.3", "paged_decode"),
    ([], "prefill_attn.7", "prefill_attn"),
    ([], "paged_decode_q8.2", "paged_decode_q8"),
    (["train_step", "block", "attn", "flash_bwd_fused", "while"], "while.4",
     "flash_bwd_fused"),
    (["mixed_step", "decode_scan", "mlp", "dot_general"], "fusion.9", None),
])
def test_a_kernel_is_found_by_the_start_of_its_name(parts, instruction,
                                                    kernel):
    import re

    found, _ = sr.kernel_name(parts, instruction,
                              re.compile(sr.scope_names()["kernel"]))
    assert found == kernel


@pytest.mark.parametrize("show, opcode, moves", [
    ("copy", "copy", True), ("slice_bitcast_fusion", "fusion", True),
    ("bitcast_dynamic-update-slice_fusion", "fusion", True),
    ("fusion", "fusion", False), ("convert_reduce_fusion", "fusion", False),
    ("paged_decode (custom-call)", "custom-call", False),
    # the wait for an asynchronous slice is not counted as movement
    ("slice-done (async-done)", "async-done", False),
])
def test_what_counts_as_pure_data_movement(show, opcode, moves):
    assert sr.is_movement(show, opcode,
                          set(sr.scope_names()["movement"])) is moves


# ---------------------------------------------------------------------------
# Every reader this reducer feeds, on the tiny cells, traced on the CPU
# ---------------------------------------------------------------------------

_RUNS = {}


def _manifest():
    """The tiny cells under names of this file's own: a traced run leaves
    its trace under ``.bench_out/trace/<cell>``, and ``test_harness.py``
    traces the tiny cells too, perhaps at the same moment in another
    worker."""
    m = tiny.manifest()
    for row in m["workloads"]:
        row["name"] = "scopes-" + row["name"]
    for section in ("end_to_end", "per_layer"):
        for metric in m[section]:
            if "workloads" in metric:
                metric["workloads"] = ["scopes-" + w
                                       for w in metric["workloads"]]
    return m


def _traced(workload, monkeypatch):
    from benchmark import costs

    monkeypatch.setattr(costs, "device_peaks", lambda kind: tiny.CPU_PEAKS)
    if workload not in _RUNS:
        import jax

        _RUNS[workload] = harness.run_cell(
            _manifest(), "scopes-" + workload, 3, 1.0, 1, jax.devices(),
            trace_names=tiny.cpu_trace_names())
    return _RUNS[workload]


@pytest.mark.parametrize("standin, metric, low_high", tiny.reading_cases())
def test_each_new_reader_reports_on_the_tiny_cells(standin, metric,
                                                   low_high, monkeypatch):
    """Every reader of ``scope_reduce`` lands in the range the cell's
    stand-in gives it (``traced_readings``), traced on the CPU."""
    result = _traced(standin["cell"], monkeypatch)
    assert result["correct"] is True
    value = result["metrics"][metric]["value"]
    low, high = low_high
    assert low <= value <= high
    if metric.endswith("_ms_step") or metric.endswith("roofline") \
            or metric.endswith("prefill_p50_ms"):
        assert value > 0
    # the span metrics name the program's spans, so the idle gaps do too
    gaps = [name for name, _ in result["breakdown"]["idle_gaps"]]
    assert any(g.startswith(("train/", "inference/")) for g in gaps)
