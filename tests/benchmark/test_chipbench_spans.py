"""The program's host spans on the device trace's clock: the shift, the
innermost-span timeline, idle time and gaps named by span, on hand-made
events and on the recorded ``yi6b-chat`` trace; the two frontend
counters' readers; and a traced window at smoke size on the CPU read by
``trace_cell``."""
import gzip
from pathlib import Path

import jax
import pytest

from benchmarks.chip import harness, host_spans, xplane

HERE = Path(__file__).resolve().parent
RECORDED = HERE / "data" / "v5e-yi6b-chat.xplane.pb.gz"
CALL, PROGRAM = "PjitFunction(decode_window)", "decode_window"


def _span(name, start, end, thread=0, **stats):
    return host_spans.HostSpan(name, float(start), float(end - start),
                               thread, stats)


# Chip busy 0-10, 20-30, 50-100 of a 120 ns slice: idle 10-20, 30-50,
# 100-120.  The scheduler (thread 0) holds the spans below; thread 1
# delivers.
OPS = [("fusion.1", 0.0, 10.0), ("fusion.2", 20.0, 10.0),
       ("fusion.3", 50.0, 50.0)]
MODULES = [("jit_decode_window(1)", 0.0, 30.0),
           ("jit_decode_window(2)", 50.0, 50.0)]
SPANS = [_span("serve.cycle", 5, 60, cycle=1),
         _span("serve.step", 8, 28, rung=4),
         _span("serve.window.post", 10, 18, rung=4, finished=0),
         _span("serve.window.prep", 18, 21, rung=4),
         _span("serve.idle", 30, 48),
         _span("serve.cycle", 100, 110, cycle=2),
         _span("serve.deliver", 0, 120, thread=1, rid=3)]


def test_innermost_timeline_by_hand():
    sched = host_spans.on_threads_of(SPANS, "serve.cycle")
    assert all(s.thread == 0 for s in sched) and len(sched) == 6
    assert host_spans.innermost(sched) == [
        (5.0, 8.0, "serve.cycle"), (8.0, 10.0, "serve.step"),
        (10.0, 18.0, "serve.window.post"),
        (18.0, 21.0, "serve.window.prep"), (21.0, 28.0, "serve.step"),
        (28.0, 30.0, "serve.cycle"), (30.0, 48.0, "serve.idle"),
        (48.0, 60.0, "serve.cycle"), (100.0, 110.0, "serve.cycle")]


def test_idle_time_and_gaps_named_by_span_by_hand():
    dev = xplane.DeviceTrace(0, OPS, MODULES)
    seg = host_spans.innermost(host_spans.on_threads_of(SPANS,
                                                        "serve.cycle"))
    by = host_spans.idle_by_span(dev, 0.0, 120.0, seg)
    assert by == pytest.approx({
        "serve.window.post": 8e-9, "serve.window.prep": 2e-9,
        "serve.idle": 18e-9, "serve.cycle": 12e-9,
        host_spans.NO_SPAN: 10e-9})
    assert host_spans.name_gaps(dev, 0.0, 120.0, seg, "serve.idle") == [
        ("waiting serve.idle", 20e-9), ("host serve.cycle", 20e-9),
        ("host serve.window.post", 10e-9)]
    # No spans: the names are those the benchmark gives without them.
    assert host_spans.name_gaps(dev, 0.0, 120.0, [], "serve.idle") \
        == xplane.idle_gaps(dev, 0.0, 120.0)
    # A gap no span covers keeps its old name beside named ones.
    late = host_spans.innermost([_span("serve.idle", 30, 50)])
    assert host_spans.name_gaps(dev, 0.0, 120.0, late, "serve.idle",
                                n=2) == [
        ("waiting serve.idle", 20e-9),
        ("unattributed, after jit_decode_window(2)", 20e-9)]


def test_shift_pairs_each_call_with_the_next_run_by_hand():
    dev = xplane.DeviceTrace(0, [], [("jit_decode_window(1)", 100.0, 50.0),
                                     ("jit_prefill_step(2)", 200.0, 10.0),
                                     ("jit_decode_window(1)", 300.0, 50.0)])
    calls = [_span(CALL, 101, 140), _span(CALL, 101.5, 139),
             _span(CALL, 302, 320), _span("serve.step", 90, 400)]
    assert [s.start for s in host_spans.outermost(calls, CALL)] == [101.0,
                                                                    302.0]
    assert host_spans.shift_of(dev, calls, CALL, PROGRAM) == -2.0
    late = [_span(CALL, 95, 99), _span(CALL, 160, 170)]
    assert host_spans.shift_of(dev, late, CALL, PROGRAM) == 0.0
    assert host_spans.shift_of(dev, calls[3:], CALL, PROGRAM) is None
    moved = host_spans.clip(host_spans.shifted(calls, -2.0), 100.0, 305.0,
                            100.0)
    assert [(s.start, s.dur) for s in moved] == [
        (0.0, 38.0), (0.0, 37.0), (200.0, 5.0), (0.0, 205.0)]


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "chat.xplane.pb"
    path.write_bytes(gzip.decompress(RECORDED.read_bytes()))
    return str(path)


def test_the_recorded_trace_shift_and_unchanged_reduction(recorded):
    """The recorded trace holds JAX's host events but no program span:
    two decode-window calls (each with its nested inner event) start
    0.686 and 0.701 ms after the chip ran them, so the shift is
    -0.701 ms; the device reduction reads as before."""
    host = host_spans.load(recorded)
    assert [s.name for s in host] == [CALL] * 4
    assert [s.start for s in host_spans.outermost(host, CALL)] == [
        103660347.0, 284424504.0]
    devs = xplane.load(recorded)
    shift = host_spans.shift_of(devs[0], host, CALL, PROGRAM)
    assert shift == 283723050.0 - 284424504.0
    assert shift / 1e6 == pytest.approx(-0.701, abs=5e-4)
    assert [(d.device, len(d.ops), len(d.modules)) for d in devs] == [
        (0, 20475, 3)]
    assert xplane.busy_ns(devs[0].ops) == 408229551.0
    seg = host_spans.innermost(host_spans.on_threads_of(host, "serve.cycle"))
    assert seg == []
    assert host_spans.name_gaps(devs[0], 0.0, 459545808.0, seg,
                                "serve.idle") \
        == xplane.idle_gaps(devs[0], 0.0, 459545808.0)


def _ctx(stats0, stats1):
    return harness.LayerContext(
        spec={}, chips=1, tp=1, peaks=None, devices=[], slice_s=1.0,
        stats0=stats0, stats1=stats1, decode_contexts=[], prompt_lens=[],
        memory_peak_bytes=0)


@pytest.mark.parametrize("name,key", [("submit_lock_p50_ms",
                                       "submit_lock_s"),
                                      ("admit_wait_p50_ms", "admit_wait_s")])
def test_the_frontend_counter_readers_by_hand(name, key):
    """The median over the entries the slice added, in ms; nothing read
    where the program keeps no such counter (as before this change) or
    the slice added none."""
    read = harness.metric_reader(name)

    def stats(*waits):
        return {"engine": {"frontend": {key: list(waits)}}}

    assert read(_ctx(stats(9.0), stats(9.0, 0.004, 0.001, 0.003))) \
        == pytest.approx(3.0)
    assert read(_ctx(stats(), stats(0.002, 0.001))) == pytest.approx(1.5)
    assert read(_ctx(stats(9.0), stats(9.0))) is None
    assert read(_ctx({"engine": {}}, {"engine": {}})) is None
    assert read(_ctx({}, {})) is None


def test_a_traced_smoke_window_reads_its_spans_on_the_cpu(monkeypatch):
    """``trace_cell`` over the chat cell at smoke size: the profile holds
    the scheduler's spans, and the frontend's counters read (the CPU has
    no peaks; no metric read here needs them)."""
    from chipbench_smoke import smoke_cell

    from benchmarks.chip import trace_cell

    monkeypatch.setattr(harness, "peaks_of", lambda kind: None)
    cell, cfg = smoke_cell()
    cell.per_layer = [m for m in cell.per_layer if m["name"] in (
        "submit_lock_p50_ms", "admit_wait_p50_ms")]
    line = trace_cell.run(cell, 2**31 + 9, 4.0, cfg, jax.devices()[:1])
    counts = line["spans"]["span_counts"]
    for name in ("serve.cycle", "serve.intake", "serve.prefill",
                 "serve.step", "serve.admit", "serve.window.prep",
                 "serve.window.dispatch", "serve.window.sync",
                 "serve.window.post", "serve.emit", "serve.deliver",
                 "serve.submit", "serve.submit.lock"):
        assert counts.get(name, 0) > 0, (name, counts)
    assert set(line["metrics"]) == {"submit_lock_p50_ms",
                                    "admit_wait_p50_ms"}
    assert line["failed"] == 0
    assert line["spans"]["submit_lock_p50_ms_due"] >= 0.0
    assert line["spans"]["admit_wait_p50_ms_due"] >= 0.0
