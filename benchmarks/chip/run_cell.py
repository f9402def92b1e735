"""Run one benchmark cell on the chips of this machine.

    python3 benchmarks/chip/run_cell.py --workload yi6b-chat --seed 7 \\
        --seconds 40 --trace 0

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of a steady slice of the window.
Every run checks what it served, the tokens and, where the program
delivers them, the logits they were served by, against the float32
reference (``harness.check``).  The last
line of standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, ``breakdown`` when traced, and the
numbers compared beside their limits under ``checks``); the last lines of
standard error are those numbers again.  ``--control fp8`` (or ``int8``)
runs the same, but judges the float32 reference's float8 (int8) twin in
the program's place, which has to come out not correct.  A run that
finds no TPU, or fewer chips than the cell asks for, exits 2 and prints
no result.
"""
from __future__ import annotations

import time

PROCESS_T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# The persistent compilation cache: where the environment says, else a
# fixed directory inside the checkout (its path is part of the key).
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("int8", "fp8"), default=None,
                    help="judge the reference at this precision in the "
                    "program's place (the control, which has to fail)")
    args = ap.parse_args(argv)

    from benchmarks.chip import harness

    cell = harness.load_cell(args.workload)
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run_cell: no TPU (JAX found {devices[0].platform}); the "
              "benchmark never falls back to the CPU", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"run_cell: {cell.name} needs {cell.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    compiles = harness.CompileCounter()
    cfg = harness.program_config(cell.spec)
    harness.log(f"[device] {devices[0].device_kind} x{len(devices)}; jax "
                f"{jax.__version__}; compile cache "
                f"{os.environ['JAX_COMPILATION_CACHE_DIR']}")
    line = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                       args.control, cfg=cfg, devices=devices[:cell.chips],
                       process_t0=PROCESS_T0, compiles=compiles)
    for name, c in line["checks"].items():
        harness.log(f"[check] {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
