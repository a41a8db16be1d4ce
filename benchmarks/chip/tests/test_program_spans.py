"""The program's own spans (``serve.*``) joined with the device's idle
gaps, and the three readers built on the program's spans and counters:
on a small recorded trace, in an untraced run, and in a tiny traced run
on the CPU."""

import dataclasses
import types

import pytest

import program_spans
import spec
import trace as bench_trace
from test_run import run
from trace import Device, Event, Trace

MS = 1e6  # ns


def ev(name, a, b):
    return Event(name, a * MS, b * MS)


@pytest.fixture
def recorded():
    # window 0..100 ms; the device runs 10..40 (decode), 48..50 (sampler)
    # and 70..90 (prefill); the benchmark's own spans are replaced
    ops = [ev("%fusion.1 = bf16[32,4096] fusion()", 10, 40),
           ev("%fusion.4 = s32[32] fusion(f32[32,49152] %p)", 48, 50),
           ev("%fusion.9 = bf16[4,2048,4096] fusion()", 70, 90)]
    tr = Trace((0.0, 100 * MS), [Device("/device:TPU:0", ops, [])],
               [ev("bench.window", 0, 100), ev("bench.sample", 41, 52)])
    spans = [ev("serve.step", -20, 60),            # opened before the window
             ev("serve.decode", 5, 40),
             ev("serve.sample", 40, 58),
             ev("serve.sample.upload", 40, 44),
             ev("serve.sample.dispatch", 44, 46),
             ev("serve.sample.wait", 46, 52),
             ev("serve.sample.download", 52, 56),
             ev("serve.step", 60, 95),
             ev("serve.admit", 61, 94),
             ev("serve.prefill", 62, 66),
             ev("serve.step", 120, 130)]           # after the window
    return tr, spans


def test_spans_are_clipped_to_the_window(recorded):
    tr, spans = recorded
    got = program_spans.clip(tr, spans)
    assert [(s.name, s.start / MS, s.end / MS) for s in got][:1] == \
        [("serve.step", 0.0, 60.0)]
    assert len(got) == len(spans) - 1


def test_idle_gaps_go_to_the_innermost_program_span(recorded):
    # gaps: 0..10 (mid 5: decode opens at 5), 40..48 (mid 44: upload ends
    # and dispatch starts at 44, the shorter is dispatch), 50..70 (mid 60:
    # the two steps meet at 60, the shorter is the second), 90..100 (mid
    # 95: the second step ends at 95)
    tr, spans = recorded
    got = program_spans.join(tr, spans)
    assert got == pytest.approx({"serve.decode": 0.010,
                                 "serve.sample.dispatch": 0.008,
                                 "serve.step": 0.030})
    assert not any(k.startswith("bench.") for k in got)


def test_every_span_name_is_kept(recorded):
    # more names than idle_by_span's default top of 10: 12 spans of 8 ms,
    # each idle for its second half, and 98..100 idle outside any span
    tr, _ = recorded
    spans = [ev(f"serve.x{i}", 8 * i, 8 * i + 8) for i in range(12)]
    ops = [ev("%fusion.1 = f32[4] fusion()", 8 * i, 8 * i + 4)
           for i in range(12)] + [ev("%fusion.2 = f32[4] fusion()", 96, 98)]
    tr = dataclasses.replace(tr, devices=[Device("/device:TPU:0", ops, [])])
    got = program_spans.join(tr, spans)
    assert len(got) == 13 and got["no span"] == pytest.approx(0.002)
    assert all(got[s.name] == pytest.approx(0.004) for s in spans)


def _untraced_run():
    from repro.serving.metrics import LatencyStats, PhaseStats
    snap = types.SimpleNamespace(
        phases={"serve.admit": PhaseStats(3, 0.3, 0.2)},
        prefill_wait=LatencyStats.of([0.1, 0.2]))
    return types.SimpleNamespace(trace=None, snapshot=snap)


@pytest.mark.parametrize("metric", ["sample_idle_ms.decode", "admit_ms.chat",
                                    "prefill_wait_p95_ms.chat"])
def test_readers_report_nothing_in_an_untraced_run(metric):
    assert spec.reader(metric)(_untraced_run()) is None


@pytest.mark.parametrize("metric", ["sample_idle_ms.decode", "admit_ms.chat",
                                    "prefill_wait_p95_ms.chat"])
def test_readers_report_nothing_from_a_program_without_spans(metric, monkeypatch):
    # a program older than these readers: no serve.* span in the trace
    # and no phases or prefill_wait in the snapshot
    tr = Trace((0.0, 100 * MS), [Device("/device:TPU:0", [], [])], [])
    r = types.SimpleNamespace(trace=tr, snapshot=types.SimpleNamespace(),
                              _cache={})
    r._once = lambda k, fn: r._cache.setdefault(k, fn())
    monkeypatch.setattr(program_spans, "load", lambda path=None: [])
    assert spec.reader(metric)(r) is None


def test_chat_traced_run_reports_the_admission_counters():
    res = run("chat", traced=True)
    m = res["metrics"]
    assert m["admit_ms.chat"]["value"] > 0
    assert m["prefill_wait_p95_ms.chat"]["value"] > 0
    assert m["admit_ms.chat"]["unit"] == "ms"


def test_batch_traced_run_reports_sampler_idle(monkeypatch):
    """The CPU trace has no device plane, so a stand-in device is busy over
    the whole window but for the program's own ``serve.sample.wait`` spans,
    read from the run's trace: every idle gap then falls in one."""
    load = bench_trace.load

    def with_device(path, **k):
        tr = load(path, **k)
        waits = sorted((s for s in program_spans.load(path)
                        if s.name == "serve.sample.wait"),
                       key=lambda s: s.start)
        ops, t = [], tr.window[0]
        for w in waits:
            if w.start > t:
                ops.append(Event("%fusion.1 = f32[4] fusion()", t, w.start))
            t = max(t, w.end)
        ops.append(Event("%fusion.1 = f32[4] fusion()", t, tr.window[1]))
        return dataclasses.replace(
            tr, devices=[Device("/device:TPU:0", ops, [])])
    monkeypatch.setattr(bench_trace, "load", with_device)
    res = run("batch", traced=True)
    assert res["metrics"]["sample_idle_ms.decode"]["value"] > 0
