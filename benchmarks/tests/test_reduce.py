"""The reduction from a trace to numbers, on a small synthetic trace with
hand-computed answers.

The trace is written as an ``XSpace`` text proto and read back through
``jax.profiler.ProfileData`` — the path a real ``.xplane.pb`` takes — with
the plane, line and instruction names a "TPU v5 lite" trace has under jax
0.9.0 (copied from the first traces of PR 22): instructions named by their
whole HLO text, a layer scan's body nested inside its ``while``, one chip with
a synchronous all-reduce and one with an asynchronous one.

Per chip and step (times in ns from the step's start; two traced steps at
1000 and 2100, after a lead-in step at 0 that must fall outside the window):

    while.6                 0..800    self 10 (its body covers 790)
      convolution_add_fusion.11 (kOutput)   0..300   matmul
      apex_flash_fwd.13                     300..400
      apex_flash_bwd_fused.10               400..700
      fusion.7 (kLoop)                      700..790
    all-reduce                800..900   chip 0: synchronous
                                         chip 1: the -done half; in flight on
                                         the asynchronous line 700..900
    copy.3                    900..1000

Window 1000..3100 = 2100 ns, busy 2000, so idle 100/2100.
"""
import collections

import pytest
from jax.profiler import ProfileData

from benchmarks import flash, reduce

CONV = ('%convolution_add_fusion.11 = bf16[96,512,4096]{2,1,0:T(8,128)(2,1)} '
        'fusion(bf16[4096]{0:T(1024)(128)(2,1)} %get-tuple-element.1113), '
        'kind=kOutput, calls=%fused_computation.84.clone.clone')
FLASH_FWD = ('%apex_flash_fwd.13 = (bf16[1536,512,64]{2,1,0:T(8,128)(2,1)}, '
             'f32[1536,512,1]{2,1,0:T(8,128)}) custom-call(s32[1]{0:T(128)} '
             '%bitcast.349), custom_call_target="tpu_custom_call"')
FLASH_BWD = ('%apex_flash_bwd_fused.10 = (f32[1536,4,512,64]{3,2,1,0:T(8,128)}'
             ', bf16[1536,512,64]{2,1,0:T(8,128)(2,1)}) custom-call(s32[1]{0} '
             '%bitcast.1), custom_call_target="tpu_custom_call"')
LOOP = ('%fusion.7 = f32[96,512]{1,0:T(8,128)} fusion(f32[96,512]{1,0} '
        '%copy-done.14), kind=kLoop, calls=%fused_computation.2')
WHILE = ('%while.6 = (s32[]{:T(128)}, bf16[96,512,1024]{2,1,0:T(8,128)(2,1)}) '
         'while((s32[]{:T(128)}, bf16[96,512,1024]{2,1,0}) %tuple.1), '
         'condition=%cond.1, body=%body.1')
COPY = ('%copy.3 = bf16[96,512,1024]{1,2,0:T(8,128)(2,1)} copy(bf16[96,512,'
        '1024]{2,1,0:T(8,128)(2,1)} %get-tuple-element.1080)')
ALL_REDUCE = ('%all-reduce.1 = bf16[24,1024,4096]{2,1,0:T(8,128)(2,1)} '
              'all-reduce(bf16[24,1024,4096]{2,1,0} %fusion.9), channel_id=1, '
              'replica_groups={{0,1}}, to_apply=%add.1')
ALL_REDUCE_START = ('%all-reduce-start.1 = bf16[24,1024,4096]{2,1,0} '
                    'all-reduce-start(bf16[24,1024,4096]{2,1,0} %fusion.9), '
                    'channel_id=1, to_apply=%add.1')
ALL_REDUCE_DONE = ('%all-reduce-done.1 = bf16[24,1024,4096]{2,1,0} '
                   'all-reduce-done(bf16[24,1024,4096]{2,1,0} '
                   '%all-reduce-start.1)')
STEP = "jit_train_step(4017238480909973269)"


def _step_ops(t, collective):
    return [(WHILE, t, 800), (CONV, t, 300), (FLASH_FWD, t + 300, 100),
            (FLASH_BWD, t + 400, 300), (LOOP, t + 700, 90),
            (collective, t + 800, 100), (COPY, t + 900, 100)]


def _planes():
    """``{plane: {line: [(name, start_ns, dur_ns)]}}``."""
    modules = [(STEP, 0, 1000), ("jit_convert_element_type(9)", 900, 50),
               (STEP, 1000, 1000), (STEP, 2100, 1000)]
    starts = (0, 1000, 2100)
    return {
        "/device:TPU:0": {
            "XLA Modules": modules,
            "XLA Ops": [op for t in starts for op in _step_ops(t, ALL_REDUCE)],
        },
        "/device:TPU:1": {
            "XLA Modules": modules,
            "XLA Ops": [op for t in starts
                        for op in _step_ops(t, ALL_REDUCE_DONE)],
            "Async XLA Ops": [(ALL_REDUCE_START, t + 700, 200)
                              for t in starts],
        },
        "/host:CPU": {
            "python3": [("bench.dispatch", 990, 20),
                        ("bench.sync", 1010, 2190), ("not.ours", 0, 5000)],
        },
    }


def _text_proto(planes) -> str:
    """An ``XSpace`` in text form: every distinct name is one
    ``event_metadata`` entry, every event an offset and a duration in ps."""
    out = []
    for plane_id, (plane, lines) in enumerate(planes.items(), 1):
        ids = {}
        body = []
        for line_id, (line, events) in enumerate(lines.items(), 1):
            evs = []
            for name, start, dur in events:
                meta = ids.setdefault(name, len(ids) + 1)
                evs.append(f"events {{ metadata_id: {meta} offset_ps: "
                           f"{start * 1000} duration_ps: {dur * 1000} }}")
            body.append(f'lines {{ id: {line_id} name: "{line}" '
                        f'{" ".join(evs)} }}')
        for name, meta in ids.items():
            escaped = name.replace("\\", "\\\\").replace('"', '\\"')
            body.append(f"event_metadata {{ key: {meta} value {{ id: {meta} "
                        f'name: "{escaped}" }} }}')
        out.append(f'planes {{ id: {plane_id} name: "{plane}" '
                   f'{" ".join(body)} }}')
    return "\n".join(out)


@pytest.fixture(scope="module")
def trace():
    profile = ProfileData.from_text_proto(_text_proto(_planes()))
    return reduce.Trace(reduce.lines_of(profile), n_steps=2)


def test_window_is_the_last_steps_of_the_biggest_program(trace):
    assert [d.index for d in trace.devices] == [0, 1]
    for dev in trace.devices:
        assert (dev.t0, dev.t1) == (1000.0, 3100.0)
    assert trace.window_s == pytest.approx(2100e-9)


def test_busy_and_idle_are_the_union_of_operations(trace):
    assert trace.busy_s == pytest.approx(2000e-9)      # nested ops count once
    assert [d.gaps() for d in trace.devices] == [[(2000.0, 2100.0)]] * 2
    assert trace.idle_gaps() == [["bench.sync", pytest.approx(100e-9)]]


def test_self_time_takes_a_scan_body_out_of_its_while(trace):
    dev = trace.devices[0]
    selfs = collections.Counter()
    for ev, ns in dev.selfs:
        selfs[reduce.instruction(ev.name)[0]] += ns
    assert selfs == {"while.6": 20, "convolution_add_fusion.11": 600,
                     "apex_flash_fwd.13": 200, "apex_flash_bwd_fused.10": 600,
                     "fusion.7": 180, "all-reduce.1": 200, "copy.3": 200}
    assert sum(selfs.values()) == dev.busy_ns


def test_category_and_kernel_shares(trace):
    matmul = trace.share_of_busy(lambda ev: reduce.op_class(ev) == "matmul")
    assert matmul == pytest.approx(100.0 * 600 / 2000)
    assert trace.share_of_busy(flash.is_flash) == pytest.approx(
        100.0 * 800 / 2000)
    dev = trace.devices[0]
    assert dev.count(lambda ev: flash.kernel_of(ev) == "fwd") == 2
    assert dev.count(lambda ev: flash.kernel_of(ev) == "bwd_fused") == 2
    assert dict(map(tuple, trace.top_ops(3))) == {
        "apex_flash_bwd_fused custom-call x2": 600e-9,
        "convolution_add_fusion fusion/kOutput x2": 600e-9,
        "apex_flash_fwd custom-call x2": 200e-9}


def test_exposed_communication_is_collective_minus_compute(trace):
    sync, overlapped = trace.devices
    # chip 0: nothing runs beside the synchronous all-reduce
    assert sync.exposed_comm_ns() == 200
    # chip 1: in flight 700..900 of each step, fusion.7 covers 700..790
    assert overlapped.exposed_comm_ns() == 2 * (200 - 90)
    share = 100.0 * trace.mean(lambda d: d.exposed_comm_ns() / d.window_ns)
    assert share == pytest.approx(100.0 * 210 / 2100)


def test_collective_bytes_are_the_operands_of_what_starts_a_transfer(trace):
    # bf16[24,1024,4096]: 24 x 1024 x 4096 x 2 bytes a step, on both chips —
    # the synchronous all-reduce, and the -start of the asynchronous one
    # (its -done moves nothing)
    payload = 24 * 1024 * 4096 * 2
    assert [d.comm_bytes() for d in trace.devices] == [2 * payload] * 2
    assert trace.n_steps == 2
    assert reduce.operand_bytes(ALL_REDUCE_DONE) == payload
    assert reduce.type_bytes("(bf16[1024]{0:T(1024)(128)(2,1)S(1)}, "
                             "/*index=1*/f32[]{:T(128)}, s32[2,3]{1,0})") \
        == 2048 + 4 + 24
    assert reduce.operand_bytes(FLASH_FWD) == 4
    with pytest.raises(KeyError):
        reduce.type_bytes("c128[4]{0}")


def test_flash_roofline_share(trace):
    class FakeJob:
        facts = {"attention": {"batch_heads": 1, "seq": 100, "head_dim": 10,
                               "causal": False, "itemsize": 2}}

    class FakeRun:
        job, peaks = FakeJob, {"bf16_flops_per_s": 1e13,
                               "hbm_bytes_per_s": 1e12}
    FakeRun.trace = trace
    # forward: 2 products x 2 x 100 x 100 x 10 = 4e5 FLOPs -> 40 ns against
    # 8000 bytes -> 8 ns: compute-bound; two calls took 100 ns each
    assert flash.roofline_share(FakeRun, "fwd") == pytest.approx(40.0)
    # backward: 5 products -> 1e6 FLOPs -> 100 ns; two calls of 300 ns
    assert flash.roofline_share(FakeRun, "bwd") == pytest.approx(100 / 3)
    FakeJob.facts = {"attention": None}
    assert flash.roofline_share(FakeRun, "fwd") is None


def test_instruction_text_is_parsed():
    assert reduce.instruction(FLASH_FWD)[::2] == ("apex_flash_fwd.13",
                                                  "custom-call")
    name, result, opcode, kind = reduce.instruction(CONV)
    assert (name, opcode, kind) == ("convolution_add_fusion.11", "fusion",
                                    "kOutput")
    assert result.startswith("bf16[96,512,4096]")
    assert reduce.instruction(WHILE)[2] == "while"
    assert reduce.instruction(STEP) == ("jit_train_step", "", "", "")
    classes = {reduce.op_class(reduce.Event(n, 0, 1, {}))
               for n in (ALL_REDUCE, ALL_REDUCE_START, ALL_REDUCE_DONE)}
    assert classes == {"collective"}
    assert reduce.op_class(reduce.Event(LOOP, 0, 1, {})) == "other"
    assert reduce.op_class(reduce.Event(FLASH_BWD, 0, 1, {})) == "kernel"


def test_interval_arithmetic():
    merged = reduce.merge([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)])
    assert merged == [(0, 3), (5, 8)]
    assert reduce.subtract([(0, 10)], [(2, 3), (5, 8)]) == [
        (0, 2), (3, 5), (8, 10)]
    assert reduce.subtract(merged, [(0, 10)]) == []
    assert reduce.clip(merged, 2, 6) == [(2, 3), (5, 6)]
    assert reduce.total(merged) == 6
