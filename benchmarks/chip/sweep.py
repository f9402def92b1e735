"""The one-off knee sweep of an open-loop cell on the chip.

    python3 benchmarks/chip/sweep.py --workload yi6b-chat --seconds 20 \\
        --rates 2 3 4 5 6 7

One set-up, then the cell's traffic at each rate for ``--seconds``: the
time to first token and between tokens, the output tokens per second,
and the backlog at the close (requests due whose first token has not
come).  The knee is the highest rate the system sustains: every
request due in the window finished within the drain, the backlog at the
close is at most the engine's slots, and the p95 time to first token is
at most four times that of the lightest rate.  The cell then runs at
four fifths of it; the knee and the rate chosen are recorded in
``traffic/<cell>.json``.  One line of JSON per rate on standard output,
then one with the knee.
"""
from __future__ import annotations

import time

PROCESS_T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))

DRAIN_S = 10.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    import jax

    from benchmarks.chip import harness

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("sweep: needs the cell's TPU chips", file=sys.stderr)
        return 2
    if cell.traffic["loop"] != "open":
        print("sweep: a closed loop has no rate to sweep", file=sys.stderr)
        return 2
    cfg = harness.program_config(cell.spec)
    served = harness.setup(cell, args.seed, cfg, devices[:cell.chips])
    harness.log(f"[sweep] set-up {time.perf_counter() - PROCESS_T0:.1f} s")
    rows = []
    try:
        for k, rate in enumerate(args.rates):
            at_rate = dataclasses.replace(
                cell, traffic=dict(cell.traffic, rate_per_s=rate))
            win = harness.run_window(served, at_rate, args.seed + k,
                                     args.seconds, cfg.vocab_size,
                                     drain_s=DRAIN_S)
            e2e = harness.end_to_end(win)
            backlog = sum(1 for r in win.recs if r.due < win.t1
                          and not any(t <= win.t1 for t in r.times))
            handles = [r.handle for r in win.recs if r.handle is not None]
            for h in handles:
                if not h.done:
                    h.cancel()
            for h in handles:
                h.result(timeout=60)
            rows.append(dict(e2e, rate_per_s=rate, backlog_at_close=backlog))
            print(json.dumps(rows[-1]), flush=True)
    finally:
        served.frontend.shutdown(drain=False)
    knee = knee_of(rows, cell.traffic["engine"]["max_slots"])
    print(json.dumps({"knee_per_s": knee,
                      "rate_per_s": None if knee is None
                      else round(0.8 * knee, 3)}), flush=True)
    return 0


def knee_of(rows, slots: int):
    """The highest rate of ``rows`` (sorted by rate) that the system
    sustains, by the rule in the module's docstring; ``None`` if none."""
    rows = sorted(rows, key=lambda r: r["rate_per_s"])
    if not rows:
        return None
    base = rows[0]["ttft_p95_ms"]
    knee = None
    for r in rows:
        if (r["failed"] == 0 and r["backlog_at_close"] <= slots
                and r["ttft_p95_ms"] <= 4 * base):
            knee = r["rate_per_s"]
        else:
            break
    return knee


if __name__ == "__main__":
    raise SystemExit(main())
