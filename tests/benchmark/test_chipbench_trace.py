"""The reduction from a profiler trace to device times, on hand-made
events and on a short trace of the ``yi6b-chat`` cell recorded on one
TPU v5e (0.36 s: three decode windows at rung 4)."""
import gzip
from pathlib import Path

import pytest

from benchmarks.chip import xplane

HERE = Path(__file__).resolve().parent
RECORDED = HERE / "data" / "v5e-yi6b-chat.xplane.pb.gz"


def test_busy_union_clip_and_matching_by_hand():
    ops = [("fusion.1", 0.0, 10.0), ("fusion.2", 5.0, 10.0),   # 0-15
           ("paged_attn_32h_128p", 20.0, 5.0),                 # 20-25
           ("copy", 24.0, 0.0), ("fusion.1", 40.0, 20.0)]      # 40-60
    assert xplane.busy_intervals(ops) == [(0.0, 15.0), (20.0, 25.0),
                                          (40.0, 60.0)]
    assert xplane.busy_ns(ops) == 40.0
    clipped = xplane.clip(ops, 8.0, 50.0)
    assert xplane.busy_ns(clipped) == 7.0 + 5.0 + 10.0
    assert xplane.clip(ops, 8.0, 50.0, origin=8.0)[0] == ("fusion.1", 0.0,
                                                          2.0)
    assert xplane.time_matching(ops, "paged_attn") == 5.0
    assert xplane.time_matching(ops, r"^fusion") == 40.0
    mods = [("jit_decode_window(7)", 0.0, 25.0),
            ("jit_prefill_step(3)", 40.0, 20.0)]
    dev = xplane.DeviceTrace(0, ops, mods)
    gaps = xplane.idle_gaps(dev, 0.0, 70.0, n=2)
    assert gaps == [("unattributed, after jit_decode_window(7)", 15e-9),
                    ("unattributed, after jit_prefill_step(3)", 10e-9)]
    top = xplane.top_ops([dev, xplane.DeviceTrace(1, ops[:1], [])], n=2)
    assert top == [("fusion.1", 20e-9), ("fusion.2", 5e-9)]


def test_a_recorded_v5e_trace_reduces_as_read_by_hand(tmp_path):
    """Counts and times read from the file with ``ProfileData`` by hand:
    one chip, 20475 operations (a ``while`` holding each window's body,
    so they nest), three ``jit_decode_window`` programs, 594 calls of the
    paged-attention kernel; starts count from the profiler's start."""
    path = tmp_path / "chat.xplane.pb"
    path.write_bytes(gzip.decompress(RECORDED.read_bytes()))
    devs = xplane.load(str(path))
    assert [(d.device, len(d.ops), len(d.modules)) for d in devs] == [
        (0, 20475, 3)]
    dev = devs[0]
    assert xplane.busy_ns(dev.ops) == 408229551.0
    assert min(s for _, s, _ in dev.ops) == 41599579.0
    assert xplane.time_matching(dev.modules, r"decode_window") \
        == 56671076.0 + 175737200.0 + 175822758.0
    assert xplane.time_matching(dev.modules, r"prefill") == 0.0
    assert xplane.time_matching(dev.ops, r"^%?paged_attn") == 7803813.0
    # Clipped to [100 ms, 300 ms]: the second window whole, and the third
    # from its start at 283.72305 ms.
    mods = xplane.clip(dev.modules, 100e6, 300e6, 100e6)
    assert [(round(s), round(d)) for _, s, d in mods] == [
        (2974412, 175737200), (183723050, 16276950)]
    top = xplane.top_ops(devs, n=1)
    assert top[0][0].startswith("%bitcast_add_fusion.3 = ")
    assert top[0][1] == pytest.approx(71986745e-9)
    gaps = xplane.idle_gaps(dev, 0.0, 459545808.0, n=1)
    assert gaps[0][0] == "unattributed, at the trace start"
    assert gaps[0][1] == pytest.approx(41599579e-9)


def test_the_step_shares_of_peak_by_hand():
    """``mfu`` and ``mfu.decode`` on a made-up slice of yi-6b: two
    decode tokens at contexts 100 and 200 and one 300-token prompt in
    one second of one v5e (197 TFLOP/s)."""
    import json

    from benchmarks.chip import harness
    from benchmarks.chip.peaks import peaks_of

    spec = json.loads((HERE.parents[1] / "benchmarks/chip/configs/yi-6b.json")
                      .read_text())
    ctx = harness.LayerContext(
        spec=spec, chips=1, tp=1, peaks=peaks_of("TPU v5 lite"),
        devices=[], slice_s=1.0, stats0={}, stats1={},
        decode_contexts=[100, 200], prompt_lens=[300], memory_peak_bytes=0,
        block=harness.block_of(spec))
    layers = 2 * 32 * (4096 * 32 * 128 * 2 + 4096 * 4 * 128 * 2
                       + 3 * 4096 * 11008)
    head = 2 * 4096 * 64000
    per_ctx = 4 * 32 * 32 * 128
    decode = 2 * (layers + head) + per_ctx * 300
    prompt = 300 * layers + head + per_ctx * 300 * 301 / 2
    got = harness.metric_reader("mfu.decode")(ctx)
    assert got == pytest.approx(100 * decode / 197e12, rel=1e-12)
    got = harness.metric_reader("mfu")(ctx)
    assert got == pytest.approx(100 * (decode + prompt) / 197e12, rel=1e-12)


def test_the_frontend_readers_by_hand():
    """``ttft_p95_ms.frontend`` is the nearest-rank 95th percentile of
    the window's times to first token, ``submit_wait_p50_ms`` the median
    wait in ``submit``; with nothing to read, both read nothing."""
    from benchmarks.chip import harness

    def ctx(ttft, waits):
        return harness.LayerContext(
            spec={}, chips=1, tp=1, peaks=None, devices=[], slice_s=1.0,
            stats0={}, stats1={}, decode_contexts=[], prompt_lens=[],
            memory_peak_bytes=0, ttft_ms=ttft, submit_wait_ms=waits)

    full = ctx([float(i) for i in range(120, 0, -1)], [5.0, 1.0, 3.0, 100.0])
    assert harness.metric_reader("ttft_p95_ms.frontend")(full) == 114.0
    assert harness.metric_reader("submit_wait_p50_ms")(full) == 4.0
    assert harness.metric_reader("ttft_p95_ms.frontend")(ctx([], [])) is None
    assert harness.metric_reader("submit_wait_p50_ms")(ctx([], [])) is None
