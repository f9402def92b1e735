"""One run of one cell: set-up, the timed window, the metrics, the check.

The cell, its configuration, its traffic and its per-layer metrics are
found by name from ``BENCHMARK.json``, and the configuration's block
(the registry keys it must match, its weights' roles and shapes, its
reference and its operation counts) by the name its file gives under
``block``; nothing here knows a particular cell or architecture.  The
program under test is driven through its public serving path:
``make_engine(kind="paged")`` -> ``ServeFrontend.warmup`` ->
``ServeFrontend.submit``.  The benchmark stamps when each request was
due and when each of its tokens was delivered (``on_token``), reads the
engine's counters as differences over the traced slice, and judges the
tokens its handles hold, with the logit each was served by where the
program delivers one (``RequestHandle.token_logits``).
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import queue
import shutil
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from benchmarks.chip import traffic as traffic_mod, xplane
from benchmarks.chip.peaks import peaks_of

ROOT = Path(__file__).resolve().parents[2]

# After the window closes, requests due in it get this long to finish;
# one that has not finished by then never came.
DRAIN_S = 60.0
# Open loop: threads that send requests, so that one waiting in
# ``submit`` delays no other.
SENDERS = 16
# The traced slice: from this share of the window, for this share of it.
TRACE_FROM, TRACE_SHARE = 0.25, 0.5
TRACE_MAX_S = 8.0

def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# --------------------------------------------------------------------------
# The cell, by name
# --------------------------------------------------------------------------
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    spec: dict                 # configs/<config>.json
    block: Any                 # blocks/<spec["block"]>.py
    traffic: dict              # traffic/<traffic>.json
    end_to_end: List[dict]
    per_layer: List[dict]
    root: Path


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    spec = json.loads((root / configs[w["config"]]["file"]).read_text())
    bench_dir = root / manifest["paths"][0]
    traffic = json.loads(
        (bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(name, w["chips"], spec, block_of(spec, root), traffic,
                [m for m in manifest["end_to_end"] if _applies(m, name)],
                [m for m in manifest["per_layer"] if _applies(m, name)],
                root)


def _load(kind: str, name: str, root: Path):
    """The module ``<kind>/<name>.py`` under the benchmark, by path."""
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    path = root / manifest["paths"][0] / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module {name!r} at {path}")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: Path = ROOT) -> Callable:
    """``read(ctx)`` of ``metrics/<name>.py`` under the benchmark."""
    return _load("metrics", name, root).read


def block_of(spec: dict, root: Path = ROOT):
    """The module ``blocks/<spec["block"]>.py`` under the benchmark: what
    the configuration's layers are (see ``blocks/dense.py``).  A file
    that names no block, or one that is not there, is an error."""
    if "block" not in spec:
        raise KeyError(f"{spec.get('name')}: the configuration file names "
                       "no block")
    return _load("blocks", spec["block"], root)


def program_config(spec: dict, root: Path = ROOT):
    """The registry's configuration named by ``spec["arch"]``, checked
    against every number the file states that its block's
    ``REGISTRY_KEYS`` name."""
    from repro.configs import get_config

    cfg = get_config(spec["arch"])
    for key, attr in block_of(spec, root).REGISTRY_KEYS.items():
        if key not in spec:
            raise KeyError(f"{spec['name']}: the file states no {key}")
        if getattr(cfg, attr) != spec[key]:
            raise ValueError(f"{spec['name']}: the program runs {attr}="
                             f"{getattr(cfg, attr)!r}, the file states "
                             f"{key}={spec[key]!r}")
    return cfg


# --------------------------------------------------------------------------
# Set-up
# --------------------------------------------------------------------------
@dataclasses.dataclass
class Served:
    frontend: Any
    engine: Any
    devices: list
    tp: int


def make_mesh(spec: dict, devices: list):
    mesh = spec.get("mesh")
    if not mesh:
        return None
    import jax  # noqa: F401  (the mesh needs the backend up)
    from jax.sharding import Mesh

    shape = (mesh["data"], mesh["model"])
    n = shape[0] * shape[1]
    return Mesh(np.asarray(devices[:n]).reshape(shape), ("data", "model"))


def setup(cell: Cell, seed: int, cfg, devices: list) -> Served:
    """Weights from the seed, the engine, and a warm-up of this cell's
    shapes only."""
    import jax

    from benchmarks.chip import weights
    from repro.models import init_params
    from repro.serve import EngineOptions, make_engine, ServeFrontend

    mesh = make_mesh(cell.spec, devices)
    shapes = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    shardings = None
    if mesh is not None:
        from repro.distributed.sharding import param_specs, to_named
        shardings = to_named(param_specs(shapes, cfg, mesh, fsdp=False),
                             mesh)
    else:
        from jax.sharding import SingleDeviceSharding
        shardings = jax.tree.map(lambda _: SingleDeviceSharding(devices[0]),
                                 shapes)
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        weights.program_params(shapes, seed, cell.block, shardings))
    log(f"[setup] weights {sum(x.nbytes for x in jax.tree.leaves(params))}"
        f" bytes from seed {seed} in {time.perf_counter() - t0:.2f} s")
    eng_opts = cell.traffic["engine"]
    opts = EngineOptions(max_slots=eng_opts["max_slots"],
                         max_seq=eng_opts["max_seq"],
                         page_size=eng_opts["page_size"])
    engine = make_engine(cfg, params, kind="paged", options=opts, mesh=mesh)
    del params
    fe = ServeFrontend(engine)
    t0 = time.perf_counter()
    fe.warmup(max_prompt_len=traffic_mod.max_prompt_len(cell.traffic))
    log(f"[setup] warmup of prompts up to "
        f"{traffic_mod.max_prompt_len(cell.traffic)} tokens, rungs "
        f"{engine.rungs}: {time.perf_counter() - t0:.2f} s")
    t0 = time.perf_counter()
    prime(fe, cell.traffic, cfg.vocab_size)
    log(f"[setup] priming of the engine's first-use ops: "
        f"{time.perf_counter() - t0:.2f} s")
    tp = mesh.shape["model"] if mesh is not None else 1
    used = list(mesh.devices.flat) if mesh is not None else devices[:1]
    return Served(fe, engine, used, tp)


def prime(fe, traffic: dict, vocab: int) -> None:
    """Serve two short requests per prompt bucket: one that grows by a
    page and one that does not.  ``warmup`` compiles the prefill and
    decode programs, but the engine compiles its page-table updates,
    admission scatters and releases on first use of each page count;
    this compiles them here, in set-up, and not in the window."""
    eng = traffic["engine"]
    page, max_seq = eng["page_size"], eng["max_seq"]
    rng = np.random.default_rng(0)
    handles = []
    for k in range(1, -(-traffic_mod.max_prompt_len(traffic) // page) + 1):
        for plen, new in ((k * page - 1, 3), (k * page - page // 2, 2)):
            if 0 < plen and plen + new <= max_seq:
                prompt = rng.integers(traffic_mod.FIRST_TOKEN_ID, vocab,
                                      size=plen, dtype=np.int32)
                handles.append(fe.submit(prompt, new))
    for h in handles:
        h.result(timeout=600)


# --------------------------------------------------------------------------
# The window
# --------------------------------------------------------------------------
class Rec:
    """One request as the benchmark sees it: when it was due, when it
    was sent, when each token was delivered (host clock), and what its
    handle holds."""

    __slots__ = ("req", "due", "sent", "times", "handle", "on_last")

    def __init__(self, req, due: float):
        self.req, self.due = req, due
        self.sent = None
        self.times: List[float] = []
        self.handle = None
        self.on_last: Optional[Callable[[], None]] = None

    def on_token(self, _tok: int) -> None:
        self.times.append(time.perf_counter())
        if len(self.times) == self.req.max_new_tokens and self.on_last:
            self.on_last()

    def tokens(self) -> np.ndarray:
        return np.asarray(self.handle.tokens, np.int32)

    def logits(self) -> Optional[np.ndarray]:
        """The logit the program picked each token by, or ``None`` from
        a program whose handles deliver none."""
        got = getattr(self.handle, "token_logits", None)
        return None if got is None else np.asarray(got, np.float32)

    def ok(self) -> bool:
        return (self.handle is not None and self.handle.done
                and self.handle.result().finish_reason == "length"
                and len(self.handle.tokens) == self.req.max_new_tokens)


class CompileCounter:
    """Programs traced, compiled and loaded from the persistent cache,
    with the host time each ended: a new shape in the window is traced
    there even where its compiled program comes from the cache."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traced",
              "/jax/core/compile/backend_compile_duration": "compiled",
              "/jax/compilation_cache/cache_retrieval_time_sec": "loaded"}

    def __init__(self):
        import jax

        self.times: Dict[str, List[float]] = {k: [] for k in
                                              self.EVENTS.values()}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event in self.EVENTS:
            self.times[self.EVENTS[event]].append(time.perf_counter())

    def between(self, t0: float, t1: float) -> Dict[str, int]:
        return {k: sum(t0 <= t <= t1 for t in ts)
                for k, ts in self.times.items()}


@dataclasses.dataclass
class Window:
    t0: float
    t1: float
    recs: List[Rec]            # every request sent, in order
    lateness: List[float]      # sent - due, open loop
    deadline: float            # the end of the wait for the window's requests
    trace: Optional[dict] = None
    unsent: List[float] = dataclasses.field(default_factory=list)
    # ... due times of requests due in the window that were never sent


def _submit(fe, rec: Rec) -> None:
    rec.handle = fe.submit(rec.req.prompt, rec.req.max_new_tokens,
                           on_token=rec.on_token)
    rec.sent = time.perf_counter()


def _open_loop(fe, schedule, t0, stop, recs, lateness):
    """Send each request when it is due, from a pool of threads, so that
    a request held up in ``submit`` holds up no other: the arrivals stay
    independent of the service, as from independent clients."""
    def send(rec):
        _submit(fe, rec)
        lateness.append(rec.sent - rec.due)

    with ThreadPoolExecutor(SENDERS, thread_name_prefix="bench-send") as pool:
        for req in schedule:
            due = t0 + req.due_s
            while not stop.is_set():
                left = due - time.perf_counter()
                if left <= 0:
                    break
                stop.wait(min(left, 0.05))
            if stop.is_set():
                return
            rec = Rec(req, due)
            recs.append(rec)
            pool.submit(send, rec)


def _closed_loop(fe, gen, clients, stop, recs):
    free: "queue.Queue" = queue.Queue()
    for _ in range(clients):
        free.put(None)
    i = 0
    while not stop.is_set():
        try:
            free.get(timeout=0.05)
        except queue.Empty:
            continue
        rec = Rec(gen.request(i), time.perf_counter())
        rec.on_last = lambda: free.put(None)
        i += 1
        recs.append(rec)
        _submit(fe, rec)


def _stats(fe) -> dict:
    s = fe.stats
    return {"decode_steps": s["decode_steps"],
            "decode_compiles": s["decode_compiles"],
            "engine": s["engine"]}


def run_window(served: Served, cell: Cell, seed: int, seconds: float,
               vocab: int, trace_dir: Optional[str] = None,
               drain_s: float = DRAIN_S) -> Window:
    """Offer the cell's traffic for ``seconds`` and wait, up to
    ``drain_s`` more, for the requests due in the window to be sent and
    to finish; the load goes on meanwhile.  With ``trace_dir``, profile
    a steady slice of the window there."""
    fe = served.frontend
    spec = cell.traffic
    if spec["loop"] == "open":
        gen = traffic_mod.Traffic.for_window(spec, seed, vocab, seconds)
    else:
        gen = traffic_mod.Traffic(spec, seed, vocab)
    recs: List[Rec] = []
    lateness: List[float] = []
    stop = threading.Event()
    if spec["loop"] == "open":
        schedule = gen.schedule(seconds + drain_s)
        t0 = time.perf_counter() + 0.02
        args = (fe, schedule, t0, stop, recs, lateness)
        target = _open_loop
    else:
        t0 = time.perf_counter() + 0.02
        args = (fe, gen, spec["clients"], stop, recs)
        target = _closed_loop
    t1 = t0 + seconds
    thread = threading.Thread(target=target, args=args, name="bench-load",
                              daemon=True)
    thread.start()
    traced = None
    if trace_dir is not None:
        traced = _trace_slice(fe, t0, seconds, trace_dir)
    time.sleep(max(0.0, t1 - time.perf_counter()))
    deadline = t1 + drain_s
    n_due = None
    if spec["loop"] == "open":
        n_due = sum(t0 + r.due_s < t1 for r in schedule)
    while time.perf_counter() < deadline and (
            (n_due is not None and len(recs) < n_due)
            or any(r.sent is None for r in list(recs) if r.due < t1)):
        time.sleep(0.01)
    for rec in [r for r in list(recs) if r.due < t1]:
        if rec.handle is None:
            continue
        try:
            rec.handle.result(timeout=max(0.0, deadline - time.perf_counter()))
        except TimeoutError:
            break
    stop.set()
    thread.join(timeout=10)
    unsent = ([] if n_due is None else
              [t0 + r.due_s for r in schedule[len(recs):n_due]])
    return Window(t0, t1, recs, lateness, deadline, traced, unsent)


def _trace_slice(fe, t0: float, seconds: float, trace_dir: str) -> dict:
    import jax

    start = t0 + TRACE_FROM * seconds
    length = min(TRACE_SHARE * seconds, TRACE_MAX_S)
    time.sleep(max(0.0, start - time.perf_counter()))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    a = time.perf_counter()
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    s0 = _stats(fe)
    b = time.perf_counter()
    time.sleep(max(0.0, b + length - time.perf_counter()))
    s1 = _stats(fe)
    c = time.perf_counter()
    jax.profiler.stop_trace()
    return {"start": a, "first": b, "end": c, "stopped": time.perf_counter(),
            "stats0": s0, "stats1": s1}


# --------------------------------------------------------------------------
# End-to-end metrics
# --------------------------------------------------------------------------
def p95(values) -> float:
    """The 95th percentile by nearest rank."""
    v = sorted(values)
    return v[max(0, math.ceil(0.95 * len(v)) - 1)]


def end_to_end(win: Window) -> dict:
    """Over the requests due in the window: a request with no token, or
    never sent, counts as waiting to the end of the drain, and as
    failed."""
    due = [r for r in win.recs if r.due < win.t1]
    ttft = [((r.times[0] if r.times else win.deadline) - r.due)
            for r in due] + [win.deadline - d for d in win.unsent]
    tpot = [(r.times[-1] - r.times[0]) / (len(r.times) - 1)
            for r in due if r.ok() and len(r.times) > 1]
    toks = sum(sum(win.t0 <= t <= win.t1 for t in r.times)
               for r in win.recs)
    return {"attempted": len(due) + len(win.unsent),
            "failed": sum(not r.ok() for r in due) + len(win.unsent),
            "ttft_p95_ms": 1e3 * p95(ttft) if ttft else None,
            "ttft_p50_ms": 1e3 * float(np.median(ttft)) if ttft else None,
            "ttft_ms": sorted(1e3 * t for t in ttft),
            "tpot_p95_ms": 1e3 * p95(tpot) if tpot else None,
            "tpot_p50_ms": 1e3 * float(np.median(tpot)) if tpot else None,
            "output_tok_s": toks / (win.t1 - win.t0)}


# --------------------------------------------------------------------------
# Per-layer metrics
# --------------------------------------------------------------------------
@dataclasses.dataclass
class LayerContext:
    """What a per-layer metric's reader may read."""
    spec: dict                   # the configuration's file
    chips: int
    tp: int                      # chips that split each layer
    peaks: Any                   # peaks.ChipPeaks
    devices: List[xplane.DeviceTrace]   # the slice, from 0 ns
    slice_s: float               # the traced slice, host clock
    stats0: dict                 # engine counters at the slice's start
    stats1: dict                 # ... and at its end
    decode_contexts: List[int]   # per decode token delivered in the slice
    prompt_lens: List[int]       # per request first served in the slice
    memory_peak_bytes: int
    # Over the whole window, host clock, per request due in it: from when
    # it was due to its first token, and to when ``submit`` returned.
    ttft_ms: List[float] = dataclasses.field(default_factory=list)
    submit_wait_ms: List[float] = dataclasses.field(default_factory=list)
    # The configuration's block: the operations and bytes a reader counts.
    block: Any = None

    def per_chip_ns(self, pattern: str, line: str = "modules") -> float:
        """Mean over chips of the time of events matching ``pattern``."""
        tot = [xplane.time_matching(getattr(d, line), pattern)
               for d in self.devices]
        return sum(tot) / len(tot) if tot else 0.0


def layer_context(cell: Cell, served: Served, win: Window, tr: dict,
                  devs: List[xplane.DeviceTrace], peak_bytes: int,
                  device_kind: str) -> LayerContext:
    a, b = tr["first"], tr["end"]
    decode_ctx, prompt_lens = [], []
    for rec in win.recs:
        plen = len(rec.req.prompt)
        for j, t in enumerate(rec.times):
            if a <= t <= b:
                if j == 0:
                    prompt_lens.append(plen)
                else:
                    decode_ctx.append(plen + j)
    waits = [1e3 * (r.sent - r.due) for r in win.recs
             if r.due < win.t1 and r.sent is not None]
    return LayerContext(cell.spec, len(served.devices), served.tp,
                        peaks_of(device_kind), devs, b - a, tr["stats0"],
                        tr["stats1"], decode_ctx, prompt_lens, peak_bytes,
                        end_to_end(win)["ttft_ms"], waits, cell.block)


def reduce_trace(trace_dir: str, tr: dict) -> List[xplane.DeviceTrace]:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    devs = xplane.load(str(files[-1]))
    # Event starts count from the profiler's start (``start_trace``).
    lo = (tr["first"] - tr["start"]) * 1e9
    hi = (tr["end"] - tr["start"]) * 1e9
    span = (tr["stopped"] - tr["start"]) * 1e9
    for d in devs:
        inside = sum(0.0 <= s <= span for _, s, _ in d.ops)
        if 2 * inside < len(d.ops):
            raise RuntimeError(
                f"chip {d.device}: {len(d.ops) - inside} of {len(d.ops)} "
                f"operations start outside the trace's {span:.0f} ns; the "
                "trace's time origin is not the profiler's start")
    return [xplane.DeviceTrace(d.device, xplane.clip(d.ops, lo, hi, lo),
                               xplane.clip(d.modules, lo, hi, lo))
            for d in devs]


def per_layer(cell: Cell, ctx: LayerContext) -> Dict[str, dict]:
    """Every per-layer metric listed for the cell.  A reader returns
    ``None`` when it finds nothing to read; for a metric whose
    ``workloads`` name this cell that is an error (a program or kernel
    renamed, say), not a metric to leave out."""
    out = {}
    for m in cell.per_layer:
        value = metric_reader(m["name"], cell.root)(ctx)
        if value is None:
            if "workloads" in m:
                raise RuntimeError(f"{m['name']} found nothing to read in "
                                   f"the traced slice of {cell.name}")
            continue
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def breakdown(devs: List[xplane.DeviceTrace], slice_ns: float) -> dict:
    return {"device_ops": [list(x) for x in xplane.top_ops(devs)],
            "idle_gaps": [list(x) for x in
                          xplane.idle_gaps(devs[0], 0.0, slice_ns)]}


# --------------------------------------------------------------------------
# The check against the reference
# --------------------------------------------------------------------------
# (prompt, served tokens, served logits or None): plain numpy arrays.
Sample = List[tuple]


def check_sample(win: Window, cell: Cell, seed: int) -> Sample:
    """Requests due in the window that finished, drawn from the seed,
    with the one that served most tokens and the one with the longest
    prompt among them, copied out of the program's handles with the
    logits they were served by."""
    k = cell.traffic["check"]["requests"]
    done = [r for r in win.recs if r.due < win.t1 and r.ok()]
    chosen = set(range(len(done)))
    if len(done) > k:
        chosen = {max(range(len(done)), key=lambda i: len(done[i].times)),
                  max(range(len(done)),
                      key=lambda i: len(done[i].req.prompt))}
        rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
        for i in rng.permutation(len(done)):
            if len(chosen) >= k:
                break
            chosen.add(int(i))
    return [(np.array(done[i].req.prompt, np.int32), done[i].tokens(),
             done[i].logits()) for i in sorted(chosen)]


def served_logits(sample: Sample) -> Optional[np.ndarray]:
    """The logit served with each token of ``sample``, in the order of
    :func:`reference.teacher_forced`; ``None`` where the program delivers
    none.  A request whose handle holds another number of logits than of
    tokens reads NaN at each of its positions, which no limit admits."""
    if all(lg is None for _, _, lg in sample):
        return None
    out = []
    for _, toks, lg in sample:
        if lg is None or len(lg) != len(toks):
            log(f"[check] a request served {len(toks)} tokens with "
                f"{'no' if lg is None else len(lg)} logits")
            lg = np.full(len(toks), np.nan, np.float32)
        out.append(lg)
    return np.concatenate(out)


def reference_logits(cell: Cell, seed: int, sample: Sample, device,
                     quant: Optional[str] = None):
    """The reference's logits at every served position of ``sample``
    (``quant`` selects the control), and the tokens served there."""
    from benchmarks.chip.reference import teacher_forced

    chk, eng = cell.traffic["check"], cell.traffic["engine"]
    n_out = chk["requests"] * cell.traffic["output_len"]["max"]
    ref = cell.block.Reference(cell.spec, seed, chk["requests"],
                               eng["max_seq"], chk["block"], quant=quant,
                               device=device)
    seqs, pos, toks = teacher_forced(sample)
    return ref.logits(seqs, pos, n_out), toks


# --------------------------------------------------------------------------
# One run
# --------------------------------------------------------------------------
def device_tag(devices: list, count: int) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": count}


def memory_peak(devices: list) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


def serve(cell: Cell, seed: int, seconds: float, trace: bool, *, cfg,
          devices: list, process_t0: float, compiles: CompileCounter):
    """Set up, serve the window, measure, and free the program.  Returns
    the result line without its check, the requests to check, and the
    chip for the reference."""
    import gc

    served = setup(cell, seed, cfg, devices)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        win = run_window(served, cell, seed, seconds, cfg.vocab_size,
                         trace_dir)
        setup_s = win.t0 - process_t0
        peak = memory_peak(served.devices)
        e2e = end_to_end(win)
        stats = _stats(served.frontend)
        late = sorted(win.lateness) or [0.0]
        log(f"[setup] setup_s {setup_s:.3f}")
        log(f"[window] {seconds} s: {e2e['attempted']} requests due, "
            f"{e2e['failed']} failed; ttft p50 {e2e['ttft_p50_ms']} ms "
            f"p95 {e2e['ttft_p95_ms']} ms; tpot p50 {e2e['tpot_p50_ms']} ms "
            f"p95 {e2e['tpot_p95_ms']} ms; {e2e['output_tok_s']:.1f} tok/s;"
            f" generator late p50 {1e3 * float(np.median(late)):.2f} ms "
            f"max {1e3 * late[-1]:.2f} ms")
        log(f"[window] ttft_ms sorted: "
            f"{' '.join(f'{t:.1f}' for t in e2e['ttft_ms'])}")
        log(f"[window] lateness_ms sorted: "
            f"{' '.join(f'{1e3 * t:.1f}' for t in late)}")
        log(f"[window] compiles inside the window: "
            f"{compiles.between(win.t0, win.t1)}; engine decode_compiles="
            f"{stats['decode_compiles']}; windows="
            f"{stats['engine']['windows']}; preemptions="
            f"{stats['engine']['preemptions']}; peak HBM {peak} bytes")
        if cell.traffic["loop"] == "closed":
            log(f"[window] closed loop: ttft p95 {e2e['ttft_p95_ms']} ms "
                f"is queue wait behind {cell.traffic['clients']} clients")
        dev = device_tag(served.devices, len(served.devices))
        dev["memory_peak_bytes"] = peak
        line = {"attempted": e2e["attempted"], "failed": e2e["failed"]}
        if trace:
            tr = win.trace
            devs = reduce_trace(trace_dir, tr)
            ctx = layer_context(cell, served, win, tr, devs, peak,
                                dev["kind"])
            busy = [xplane.busy_ns(d.ops) / 1e9 for d in devs]
            for d, b in zip(devs, busy):
                log(f"[trace] chip {d.device}: busy {b:.4f} s of "
                    f"{ctx.slice_s:.4f} s")
            dev["busy_s"] = sum(busy) / len(busy)
            dev["window_s"] = ctx.slice_s
            line["metrics"] = per_layer(cell, ctx)
            line["device"] = dev
            line["breakdown"] = breakdown(devs, ctx.slice_s * 1e9)
        else:
            e2e["setup_s"] = setup_s
            line["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                           "unit": m["unit"]}
                               for m in cell.end_to_end}
            line["device"] = dev
        sample = check_sample(win, cell, seed)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
        served.frontend.shutdown(drain=False)
    ref_device = served.devices[0]
    # The handles hold the frontend, and it the engine and the weights.
    del served, win
    gc.collect()
    log(f"[check] program freed: "
        f"{(ref_device.memory_stats() or {}).get('bytes_in_use')} bytes "
        "in use before the reference")
    return line, sample, ref_device


def _worst(errs: np.ndarray) -> float:
    """The largest of ``errs``; NaN, a logit missing, reads infinite."""
    top = float(np.max(errs)) if len(errs) else 0.0
    return math.inf if math.isnan(top) else top


def check(cell: Cell, seed: int, line: dict, sample: Sample, device,
          control: Optional[str] = None) -> dict:
    """Compare what the program served in ``sample`` with the float32
    reference, teacher-forced over each prompt and its served tokens, and
    add ``correct`` and the numbers compared (``checks``, last) to
    ``line``.  ``correct`` needs every number compared within its limit:

    - ``logit_gap``: the widest gap by which the reference's logit of a
      served token lies below the reference's best there (limit
      ``check.gap_limit`` of the traffic file);
    - ``logit_err``: the widest distance between the logit the program
      served a token with and the reference's logit of that token (limit
      ``check.logit_limit``).  With tied embeddings and seeded weights
      the input token tops the logits, so wrong arithmetic can still
      serve the reference's tokens; it cannot serve its logits.  It is
      compared only where the program's handles deliver logits and the
      traffic file states the limit; otherwise a line on standard error
      says that it is not, and the rest decide;
    - ``failed_requests``: requests due in the window that did not
      finish (limit 0).

    ``control`` puts the reference at a lower precision (``"fp8"``,
    ``"int8"``) in the program's place: at each served position the token
    it ranks first, with its own logit there, is judged instead of the
    served one.  It is judged on the numbers the program's run is judged
    on: where the program delivers no logits, the control's ``logit_err``
    is logged and not compared."""
    from benchmarks.chip.reference import errs_of, gaps_of, top_picks

    chk = cell.traffic["check"]
    t0 = time.perf_counter()
    gap, err, toks = math.inf, None, []
    if sample:
        logits, toks = reference_logits(cell, seed, sample, device)
        served = delivered = served_logits(sample)
        if control is not None:
            ctrl, _ = reference_logits(cell, seed, sample, device, control)
            picks, picked = top_picks(ctrl, len(toks))
            del ctrl
            own = ("none delivered" if delivered is None else
                   repr(_worst(errs_of(logits, toks, delivered))))
            log(f"[check] control {control}: {int(np.sum(picks != toks))}"
                f" of {len(toks)} tokens differ from the served ones; the "
                "program's own logit_gap "
                f"{float(np.max(gaps_of(logits, toks)))!r}, logit_err {own}")
            toks, served = picks, picked
        gap = float(np.max(gaps_of(logits, toks)))
        if served is not None:
            err = _worst(errs_of(logits, toks, served))
        del logits
        if delivered is None or "logit_limit" not in chk:
            why = ("the program delivers no logits" if delivered is None
                   else "the traffic file states no check.logit_limit")
            read = "" if err is None else f" {err!r}"
            log(f"[check] logit_err{read} is not compared ({why}); "
                "logit_gap decides")
            err = None
    log(f"[check] reference over {len(sample)} requests, {len(toks)} "
        f"served tokens: {time.perf_counter() - t0:.2f} s")
    checks = {"logit_gap": {"value": gap, "limit": chk["gap_limit"]}}
    if err is not None:
        checks["logit_err"] = {"value": err, "limit": chk["logit_limit"]}
    checks["failed_requests"] = {"value": line["failed"], "limit": 0}
    out = {"correct": all(c["value"] <= c["limit"]
                          for c in checks.values())}
    out.update(line)
    out["checks"] = checks
    return out


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        control: Optional[str] = None, **kw) -> dict:
    """One run of ``cell``: the result line (a dict)."""
    line, sample, device = serve(cell, seed, seconds, trace, **kw)
    return check(cell, seed, line, sample, device, control)
