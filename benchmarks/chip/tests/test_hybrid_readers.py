"""The per-layer readers of the granitemoehybrid cell: the HLO op_name
look-up (``scopes.py``), each reader on a small recorded trace, each
reader silent in an untraced run and on a program without the hybrid's
counters, and a tiny traced run on the CPU with a stand-in device whose
operations are the decode and prefill programs' own instructions."""

import dataclasses
import sys
import time
import types

import jax
import pytest

import program_spans
import runner
import scopes
import spec
import tiny_hybrid
import trace as bench_trace
import weights_hybrid
from trace import Device, Event, Trace

MS = 1e6  # ns
PEAKS = {"bf16_flops": 1e12, "hbm_bytes_s": 1e11}
NEW = ("mfu.hybrid_decode", "step_roofline.hybrid_decode",
       "mamba_ms.hybrid_decode", "moe_ms.hybrid_decode",
       "expert_load.hybrid_decode", "ssd_roofline.hybrid_prefill")

HLO = '''HloModule jit_decode_pool_step, entry_computation_layout={()->()}

%fused_computation.1 (param_0: bf16[32,4096]) -> bf16[32,16768] {
  %param_0 = bf16[32,4096]{1,0} parameter(0)
  %dot.1 = bf16[32,16768]{1,0} dot(%param_0), metadata={op_name="jit(decode_pool_step)/mamba/in_proj/dot_general"}
  ROOT %convert.2 = bf16[32,16768]{1,0} convert(%dot.1), metadata={op_name="jit(decode_pool_step)/mamba/in_proj/convert"}
}

ENTRY %main.10 (p: bf16[32,4096]) -> bf16[32,4096] {
  %p = bf16[32,4096]{1,0} parameter(0)
  %fusion.1 = bf16[32,16768]{1,0} fusion(%p), kind=kOutput, calls=%fused_computation.1
  %ragged-dot-metadata.4 = (s32[10]{0}, s32[1]{0}) custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-metadata"}
  %get-tuple-element.5 = s32[10]{0} get-tuple-element(%ragged-dot-metadata.4), index=0
  %ragged-dot-none.6 = bf16[288,4096]{1,0} custom-call(%get-tuple-element.5, %p), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %fusion.2 = f32[288,4096]{1,0} fusion(%ragged-dot-none.6), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(decode_pool_step)/moe/experts/mul"}
  %sort.8 = s32[32]{0} sort(%p), metadata={op_name="sort"}
  ROOT %paged_attention.3 = bf16[32,1,32,128]{3,2,1,0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(decode_pool_step)/attn/paged_attention/pallas_call"}
}
'''


def test_op_names_resolve_fusions_through_their_computation():
    names = scopes.op_names(HLO)
    assert names["fusion.1"].endswith("/mamba/in_proj/dot_general")
    assert names["fusion.2"] == "jit(decode_pool_step)/moe/experts/mul"
    assert "/attn/" in names["paged_attention.3"]
    # the compiler's grouped-matmul calls take the path of the instruction
    # that consumes their result, through a get-tuple-element; a call no
    # instruction with a path consumes keeps the compiler's name
    assert names["ragged-dot-none.6"] == names["fusion.2"]
    assert names["ragged-dot-metadata.4"] == names["fusion.2"]
    assert names["sort.8"] == "sort"
    assert scopes.instruction("%fusion.2 = f32[288,4096] fusion(...)") == \
        "fusion.2"


def _ev(name, a, b):
    return Event(name, a * MS, b * MS)


def _recorded_run():
    """Window 0..100 ms: one decode step (program 0..40 ms: Mamba2 10 ms,
    experts 6 ms, the paged kernel 4 ms, the grouped matmuls' calls 2.5
    ms) and one batched prefill (program
    50..90 ms: the SSD kernel 10 ms, the flash kernel 5 ms)."""
    k = 'custom_call_target="tpu_custom_call"'
    ops = [_ev("%fusion.1 = bf16[32,16768] fusion(bf16[32,4096] %p)", 0, 10),
           _ev("%fusion.2 = f32[288,4096] fusion(bf16[32,4096] %p)", 10, 16),
           _ev(f"%paged_attention.3 = bf16[32,1,32,128] custom-call("
               f"s32[32,128] %bt), {k}", 16, 20),
           _ev(f"%ragged-dot-metadata.4 = (s32[10], s32[1]) custom-call("
               f"bf16[32,4096] %p), {k}", 20, 20.5),
           _ev(f"%ragged-dot-none.6 = bf16[288,4096] custom-call("
               f"s32[10] %get-tuple-element.5), {k}", 20.5, 22.5),
           _ev(f"%mamba2_ssd.7 = bf16[512,256,64] custom-call(), {k}", 60, 70),
           _ev(f"%flash_attention.2 = bf16[4,256,32,128] custom-call(), {k}",
               70, 75)]
    mods = [_ev("jit_decode_pool_step(1)", 0, 40),
            _ev("jit_prefill_batched(2)", 50, 90)]
    tr = Trace((0.0, 100 * MS), [Device("/device:TPU:0", ops, mods)], [])
    cell = tiny_hybrid.cell()
    cell.config["serve"] = dict(cell.config["serve"], max_batch=32,
                                max_len=2048)
    harness = spec.module("harness", "serve_hybrid")
    window = types.SimpleNamespace(decode_ctx=[[100] * 32],
                                   prefills=[[200, 256, 256]],
                                   programs={"decode": HLO, "prefill": ""})
    snap = types.SimpleNamespace(expert_load_mean=2.0, busy_lanes_mean=32.0,
                                 steps=1)
    return runner.Run(cell=cell, peaks=PEAKS, setup_s=1.0, window=window,
                      snapshot=snap, trace=tr,
                      classify=harness.program_class(cell.config))


def test_readers_on_a_recorded_trace():
    run = _recorded_run()
    dims = weights_hybrid.dims(run.config)
    read = lambda m: spec.reader(m)(run)
    assert read("mamba_ms.hybrid_decode") == pytest.approx(10.0)
    assert read("moe_ms.hybrid_decode") == pytest.approx(8.5)
    assert read("expert_load.hybrid_decode") == 2.0
    cnt = spec.module("counts", "granitemoehybrid")
    slots = 2.0 * dims["layers"] * dims["n_held"]            # one step
    flops = 32 * cnt.decode_token_flops(dims, 100) \
        + slots * cnt.expert_slot_flops(dims)
    assert read("mfu.hybrid_decode") == pytest.approx(
        100 * flops / (0.1 * PEAKS["bf16_flops"]))
    f, b = cnt.decode_step_work(dims, [100] * 32, slots, 2)
    assert read("step_roofline.hybrid_decode") == pytest.approx(
        100 * max(f / PEAKS["bf16_flops"], b / PEAKS["hbm_bytes_s"]) / 0.040)
    sf, sb = spec.module("counts", "mamba2_ssd").work(dims, [200, 256], 2)
    assert read("ssd_roofline.hybrid_prefill") == pytest.approx(
        100 * max(sf / PEAKS["bf16_flops"], sb / PEAKS["hbm_bytes_s"]) / 0.010)


@pytest.mark.parametrize("metric", NEW)
def test_readers_report_nothing_in_an_untraced_run(metric):
    run = dataclasses.replace(_recorded_run(), trace=None)
    assert spec.reader(metric)(run) is None


@pytest.mark.parametrize("metric", NEW)
def test_readers_report_nothing_from_a_program_without_the_hybrid(metric):
    """The parent's program: no expert-load counter in its snapshot and no
    program texts in the window."""
    run = _recorded_run()
    run.snapshot = types.SimpleNamespace(busy_lanes_mean=32.0, steps=1)
    run.window = types.SimpleNamespace(decode_ctx=[], prefills=[])
    assert spec.reader(metric)(run) is None


def test_tiny_traced_run_reports_every_new_metric(monkeypatch, tmp_path):
    """A stand-in device runs each program of the window as one module
    whose operations are that program's own HLO instructions, one
    microsecond each, so every reader finds its scope; the CPU's programs
    hold no compiled kernel, so each module also gets the kernel the
    harness tells it by (the paged kernel with the engine's s32[4,8] block
    table, the SSD kernel by name)."""
    harness = spec.module("harness", "serve_hybrid")
    # a profile of its own, so traced runs of other test files running at
    # the same time cannot overwrite it
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "trace")
    spans_load = program_spans.load
    monkeypatch.setattr(program_spans, "load",
                        lambda path=None: spans_load(tmp_path / "trace"))
    texts = {}
    program_texts = harness.program_texts

    def keep(engine):
        texts.update(program_texts(engine))
        return texts
    monkeypatch.setattr(harness, "program_texts", keep)
    load = bench_trace.load

    def with_device(path, **k):
        tr = load(path, **k)
        t0, ops, mods = tr.window[0], [], []
        k = 'custom_call_target="tpu_custom_call"'
        for cls, module, kernel in (
                ("decode", "jit_decode_pool_step(1)",
                 f"%paged_attention.0 = custom-call(s32[4,8] %bt), {k}"),
                ("prefill", "jit_prefill_batched(2)",
                 f"%mamba2_ssd.0 = custom-call(), {k}")):
            start = t0
            ops.append(Event(kernel, t0, t0 + 1e3))
            t0 += 1e3
            for line in texts[cls].splitlines():
                name = scopes._INSTR.match(line)
                if not name or "parameter(" in line:
                    continue
                ops.append(Event(line.strip(), t0, t0 + 1e3))
                t0 += 1e3
            mods.append(Event(module, start, t0))
        return dataclasses.replace(
            tr, devices=[Device("/device:TPU:0", ops, mods)])
    monkeypatch.setattr(bench_trace, "load", with_device)
    res = runner.run_cell(tiny_hybrid.cell(), 2**31 + 77, 3.0, True,
                          jax.devices()[:1], time.perf_counter(),
                          lambda m: print(m, file=sys.stderr),
                          peak_table=PEAKS)
    assert res["correct"] is True
    for m in NEW:
        assert res["metrics"][m]["value"] > 0, m
    for m in ("step_roofline.hybrid_decode", "ssd_roofline.hybrid_prefill",
              "mfu.hybrid_decode"):
        assert res["metrics"][m]["unit"] == "%"
