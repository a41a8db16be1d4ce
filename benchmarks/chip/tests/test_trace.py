"""The trace reduction on a small recorded trace: busy union, kernel
time by the program it runs in, idle gaps by the open host span.  The
events are written as a TPU trace writes them: operations named by their
HLO instruction, Pallas kernels as nameless ``tpu_custom_call``s, and a
layer scan's ``while`` listed with the operations of its body."""

import pytest

import trace as tr
from harness.serve import program_class
from trace import Device, Event, Trace

MS = 1e6  # ns
PAGED = ('%closed_call.10 = bf16[32,32,128]{2,1,0} custom-call(s32[32,128]{1,0} '
         '%copy-done, bf16[2001,128,128]{2,1,0} %bitcast.189), '
         'custom_call_target="tpu_custom_call"')
FLASH = ('%closed_call.10 = bf16[4,32,256,128]{3,2,1,0} custom-call('
         'bf16[4,32,256,128]{3,2,1,0} %q), custom_call_target="tpu_custom_call"')
CONFIG = {"serve": {"max_batch": 32, "max_len": 2048, "kv_block_size": 16}}


def ev(name, a, b):
    return Event(name, a * MS, b * MS)


@pytest.fixture
def recorded():
    # window 0..100 ms; decode program 10..40: a while 11..38 holding the
    # paged kernel 12..30 and a fusion 30..38; sampling 45..50; prefill
    # 60..90 with the flash kernel 62..80 and a fusion 80..90
    ops = [ev("%while.2 = (s32[]) while(%tuple.40), body=%region_0", 11, 38),
           ev(PAGED, 12, 30), ev("%fusion.123 = bf16[32,14336] fusion()", 30, 38),
           ev("%fusion.4 = s32[32] fusion(f32[32,49152] %p)", 45, 50),
           ev(FLASH, 62, 80), ev("%fusion.77 = bf16[4,2048,4096] fusion()", 80, 90)]
    mods = [ev("jit__lambda(417410443206248109)", 10, 40),
            ev("jit__sample_tokens(9758286808506352735)", 45, 50),
            ev("jit__lambda(5016745534144586600)", 60, 90)]
    spans = [ev("bench.window", 0, 100),
             ev("bench.engine_step", 5, 55), ev("bench.sample", 41, 52),
             ev("bench.wait_arrival", 55, 60), ev("bench.engine_step", 60, 95)]
    return Trace((0.0, 100 * MS), [Device("/device:TPU:0", ops, mods)], spans)


def test_busy_is_the_union_of_operations(recorded):
    # 11..38 + 45..50 + 62..90 = 27 + 5 + 28 ms
    assert tr.busy_s(recorded) == pytest.approx(0.060)


def test_program_time_by_the_kernel_inside(recorded):
    cls = program_class(CONFIG)
    assert tr.program_s(recorded, cls, "decode") == pytest.approx(0.030)
    assert tr.program_s(recorded, cls, "sample") == pytest.approx(0.005)
    assert tr.program_s(recorded, cls, "prefill") == pytest.approx(0.030)


def test_kernel_time_by_the_program_it_runs_in(recorded):
    cls = program_class(CONFIG)
    assert tr.kernel_s(recorded, cls, "decode") == pytest.approx(0.018)
    assert tr.kernel_s(recorded, cls, "prefill") == pytest.approx(0.018)
    assert tr.kernel_s(recorded, cls, "sample") == 0.0


def test_a_kernel_without_the_block_table_is_a_prefill(recorded):
    # a pool of another batch: the paged kernel's table no longer matches
    other = program_class({"serve": {"max_batch": 8, "max_len": 2048,
                                     "kv_block_size": 16}})
    assert tr.program_s(recorded, other, "decode") == 0.0
    assert tr.program_s(recorded, other, "prefill") == pytest.approx(0.060)


def test_idle_gaps_go_to_the_innermost_open_span(recorded):
    # gaps: 0..11 (mid 5.5, engine_step), 38..45 (mid 41.5, sample inside
    # engine_step), 50..62 (mid 56, wait_arrival), 90..100 (mid 95,
    # engine_step ends at 95 -> counted there)
    got = dict(tr.idle_by_span(recorded))
    assert got == pytest.approx({"bench.engine_step": 0.021,
                                 "bench.sample": 0.007,
                                 "bench.wait_arrival": 0.012})


def test_top_ops_count_innermost_operations_by_program_and_kind(recorded):
    top = dict(tr.top_ops(recorded, program_class(CONFIG)))
    assert top == pytest.approx({"decode/pallas_kernel": 0.018,
                                 "decode/fusion": 0.008,
                                 "sample/fusion": 0.005,
                                 "prefill/pallas_kernel": 0.018,
                                 "prefill/fusion": 0.010})


def test_leaves_drop_an_operation_that_encloses_others(recorded):
    kept = tr.leaves(recorded.devices[0].ops)
    assert [tr.kind(e) for e in kept] == ["pallas_kernel", "fusion", "fusion",
                                          "pallas_kernel", "fusion"]
    assert tr.kind(Event("jit__lambda(417)", 0, 1)) == "jit__lambda"
