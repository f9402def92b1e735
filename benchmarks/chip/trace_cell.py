"""Run one cell traced and put the chip's idle time down to the
program's spans.

    python3 benchmarks/chip/trace_cell.py --workload yi6b-chat --seed 7 \\
        --seconds 50

The cell runs as ``run_cell.py --trace 1`` runs it (set-up, the window,
a profile of its steady slice, the per-layer metrics), without the
check against the reference.  On the same trace it then loads the
program's ``serve.*`` host spans, moves them onto chip 0's clock by the
shift :func:`host_spans.shift_of` estimates from the decode windows,
and reports: the idle gaps named by the scheduler's innermost span, each
chip's idle time by span (and chip 0's by the innermost host event,
JAX's calls included), ``device_idle_host`` (the share of the slice
in which no operation ran on the chip while a scheduler span other than
the wait for work was open, mean over chips), the medians of each
request's wait in ``submit`` for the frontend's bookkeeping lock (which
no engine step holds) and in the admission queue over the requests due
in the window, the traced window's end-to-end numbers, and what a span
costs on this host with the profiler off and on.  The
last line of standard output is one JSON object; ``--out`` writes it to
a file too.
"""
from __future__ import annotations

import time

PROCESS_T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))

# The program's span names this report reads.
PREFIX, CYCLE, IDLE = "serve.", "serve.cycle", "serve.idle"
# The host call and the chip program the clock shift is estimated from.
CALL, PROGRAM = "PjitFunction(decode_window)", r"decode_window"


def span_cost(n: int = 100_000) -> dict:
    """Microseconds per span opened and closed on this host, with the
    profiler off and with it on (host tracer only)."""
    import jax

    from repro.serve.spans import span

    def per_span(k: int) -> float:
        t0 = time.perf_counter()
        for i in range(k):
            with span("serve.cost", rung=i):
                pass
        return 1e6 * (time.perf_counter() - t0) / k

    off = per_span(n)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    tmp = tempfile.mkdtemp(prefix="span-cost-")
    try:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            on = per_span(n // 5)
        finally:
            jax.profiler.stop_trace()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return {"off_us": off, "on_us": on}


def _median_ms(values) -> float | None:
    values = [v for v in values if v is not None]
    return 1e3 * statistics.median(values) if values else None


def attribute(trace_dir: str, win, devs, slice_s: float) -> dict:
    """The report on the host spans of the trace under ``trace_dir``
    (``devs``: the slice's device traces as the harness reduced them)."""
    from benchmarks.chip import host_spans, xplane

    path = str(sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1])
    host = host_spans.load(path)
    full = xplane.load(path)
    shift = host_spans.shift_of(full[0], host, CALL, PROGRAM) if full \
        else None
    tr = win.trace
    lo = (tr["first"] - tr["start"]) * 1e9
    hi = (tr["end"] - tr["start"]) * 1e9
    spans = host_spans.clip(host_spans.shifted(host, shift or 0.0),
                            lo, hi, lo)
    thread = host_spans.on_threads_of(spans, CYCLE)
    segments = host_spans.innermost(
        [s for s in thread if s.name.startswith(PREFIX)])
    slice_ns = slice_s * 1e9
    idle = [host_spans.idle_by_span(d, 0.0, slice_ns, segments)
            for d in devs]
    # Chip 0's idle time by the innermost host event of the scheduler's
    # thread, JAX's own calls (``PjitFunction(...)``) included.
    calls = [host_spans.idle_by_span(d, 0.0, slice_ns,
                                     host_spans.innermost(thread))
             for d in devs[:1]]
    host_idle = [sum(s for name, s in by.items()
                     if name not in (IDLE, host_spans.NO_SPAN))
                 for by in idle]
    counts: dict = {}
    for s in spans:
        if s.name.startswith(PREFIX):
            counts[s.name] = counts.get(s.name, 0) + 1
    due = [r for r in win.recs if r.due < win.t1 and r.handle is not None]
    return {
        "shift_ms": None if shift is None else shift / 1e6,
        "span_counts": counts,
        "idle_gaps": [list(g) for g in host_spans.name_gaps(
            devs[0], 0.0, slice_ns, segments, IDLE)] if devs else [],
        "idle_by_span_s": idle,
        "idle_by_call_s": calls,
        "device_idle_host": (100.0 * sum(host_idle) / len(host_idle)
                             / slice_s if host_idle else None),
        "submit_lock_p50_ms_due": _median_ms(
            getattr(r.handle, "lock_wait_s", None) for r in due),
        "admit_wait_p50_ms_due": _median_ms(
            r.handle.admitted_at - r.handle.queued_at for r in due
            if getattr(r.handle, "admitted_at", None) is not None),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None, help="also write the JSON here")
    args = ap.parse_args(argv)

    from benchmarks.chip import harness

    cell = harness.load_cell(args.workload)
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"trace_cell: no TPU (JAX found {devices[0].platform})",
              file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"trace_cell: {cell.name} needs {cell.chips} chips, JAX "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    cost = span_cost()
    harness.log(f"[spans] cost per span: {cost['off_us']:.3f} us off, "
                f"{cost['on_us']:.3f} us on")
    cfg = harness.program_config(cell.spec)
    line = run(cell, args.seed, args.seconds, cfg, devices[:cell.chips])
    line["span_cost_us"] = cost
    text = json.dumps(line)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(text + "\n")
    print(text, flush=True)
    return 0


def run(cell, seed: int, seconds: float, cfg, devices: list) -> dict:
    """One traced window of ``cell`` and the report on it."""
    from benchmarks.chip import harness, xplane

    served = harness.setup(cell, seed, cfg, devices)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        win = harness.run_window(served, cell, seed, seconds,
                                 cfg.vocab_size, trace_dir)
        e2e = harness.end_to_end(win)
        devs = harness.reduce_trace(trace_dir, win.trace)
        peak = harness.memory_peak(served.devices)
        dev = harness.device_tag(served.devices, len(served.devices))
        ctx = harness.layer_context(cell, served, win, win.trace, devs,
                                    peak, dev["kind"])
        metrics = harness.per_layer(cell, ctx)
        report = attribute(trace_dir, win, devs, ctx.slice_s)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
        served.frontend.shutdown(drain=False)
    harness.log(f"[spans] host shift {report['shift_ms']} ms; "
                f"device_idle_host {report['device_idle_host']} %")
    for name, s in report["idle_gaps"]:
        harness.log(f"[spans] gap {s * 1e3:.3f} ms: {name}")
    busy = [xplane.busy_ns(d.ops) / 1e9 for d in devs]
    dev.update(busy_s=sum(busy) / len(busy) if busy else None,
               window_s=ctx.slice_s, memory_peak_bytes=peak)
    return {"attempted": e2e["attempted"], "failed": e2e["failed"],
            "end_to_end_traced": {k: e2e[k] for k in (
                "ttft_p50_ms", "ttft_p95_ms", "tpot_p95_ms",
                "output_tok_s")},
            "setup_s": win.t0 - PROCESS_T0, "metrics": metrics,
            "breakdown": {"device_ops": [list(x) for x in
                                         xplane.top_ops(devs)],
                          "idle_gaps": report.pop("idle_gaps")},
            "spans": report, "device": dev}


if __name__ == "__main__":
    raise SystemExit(main())
