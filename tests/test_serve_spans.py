"""Serving spans on the profiler's host tracer (``repro.serve.spans``)
and each request's lifecycle stamps on its ``RequestHandle``.

A smoke serve through ``ServeFrontend`` over the paged engine runs under
``jax.profiler`` on the CPU; the trace is read back with
``ProfileData``: every span of the table appears, the window's spans
nest in an engine step inside a scheduler cycle, and the ``rid``/
``rids`` stats tie each request's submit, prefill and delivery together
across threads.  The engine keeps one window in flight: a step
dispatches its window and reads back the one before, and a drain reads
back the window in flight, with its reason.
"""
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.models import init_params
from repro.serve import make_engine, ServeFrontend

SPANS = ("serve.submit", "serve.submit.lock", "serve.cycle",
         "serve.intake", "serve.prefill", "serve.step", "serve.admit",
         "serve.window.prep", "serve.window.dispatch", "serve.window.sync",
         "serve.window.post", "serve.window.drain", "serve.emit",
         "serve.idle", "serve.deliver")
WINDOW_SPANS = [s for s in SPANS if s.startswith("serve.window.")]


def _rids(value):
    return {int(x) for x in str(value).split()}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    from jax.profiler import ProfileData

    cfg = smoke_config("yi-6b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = make_engine(cfg, params, kind="paged", max_slots=4, max_seq=64,
                      window=4, page_size=16)
    fe = ServeFrontend(eng)
    fe.warmup(max_prompt_len=24)
    rng = np.random.default_rng(3)
    lens, budgets = [5, 17, 9, 3, 23, 8], [4, 2, 6, 3, 5, 4]
    prompts = [rng.integers(0, cfg.vocab_size, size=s).astype(np.int32)
               for s in lens]
    out = tmp_path_factory.mktemp("profile")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(out), profiler_options=opts)
    try:
        handles = []
        for i, (p, b) in enumerate(zip(prompts, budgets)):
            handles.append(fe.submit(p, b))
            if i == 2:
                time.sleep(0.05)            # the scheduler goes idle
        done = fe.drain(timeout=120)
        time.sleep(0.05)                    # the last deliveries close
    finally:
        jax.profiler.stop_trace()
    stats = fe.stats
    fe.shutdown()
    path = sorted(Path(out).rglob("*.xplane.pb"))[-1]
    events = []                             # (name, start, end, line, stats)
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("serve."):
                    events.append((e.name, e.start_ns,
                                   e.start_ns + e.duration_ns, i,
                                   dict(e.stats)))
    return handles, done, stats, events


def test_every_span_of_the_table_is_in_the_trace(served):
    _, _, _, events = served
    names = {e[0] for e in events}
    assert set(SPANS) <= names, set(SPANS) - names


def test_window_spans_nest_in_a_step_inside_a_cycle(served):
    _, _, _, events = served

    def holder(ev, name):
        return [h for h in events if h[0] == name and h[3] == ev[3]
                and h[1] <= ev[1] and ev[2] <= h[2]]

    windows = [e for e in events if e[0] in WINDOW_SPANS]
    assert windows
    rung_of = {e[4]["window"]: e[4]["rung"] for e in windows
               if e[0] == "serve.window.dispatch"}
    for ev in windows:
        steps = holder(ev, "serve.step")
        assert len(steps) == 1, ev
        assert len(holder(steps[0], "serve.cycle")) == 1, ev
        # Each span names its window; a step dispatches its own window
        # at the step's rung and reads back an earlier one at that one's.
        assert ev[4]["rung"] == rung_of[ev[4]["window"]] > 0, ev
        if ev[0] in ("serve.window.prep", "serve.window.dispatch"):
            assert steps[0][4]["rung"] == ev[4]["rung"], ev
    assert all(e[4]["finished"] >= 0 for e in windows
               if e[0] == "serve.window.post")
    # Every window dispatched is read back once, after its dispatch.
    start = {e[4]["window"]: e[1] for e in windows
             if e[0] == "serve.window.dispatch"}
    for name in ("serve.window.sync", "serve.window.post"):
        read = [e for e in windows if e[0] == name]
        assert sorted(e[4]["window"] for e in read) == sorted(start)
        assert all(e[1] > start[e[4]["window"]] for e in read)


def test_drains_name_their_reason_and_hold_the_read_back(served):
    """A drain reads back the window in flight inside its own span: at
    least the one at the end of each burst (nothing left to decode), and
    no reason outside the engine's list.  The counters agree with the
    trace: every window dispatched with nothing in flight ends in one
    drain."""
    _, _, stats, events = served
    drains = [e for e in events if e[0] == "serve.window.drain"]
    reasons = {d[4]["reason"] for d in drains}
    assert "idle" in reasons
    assert reasons <= {"admit", "preempt", "cancel", "remesh", "reset",
                       "fault", "idle", "shutdown"}
    for d in drains:
        held = [e for e in events if e[0] in ("serve.window.sync",
                                              "serve.window.post")
                and e[3] == d[3] and d[1] <= e[1] and e[2] <= d[2]]
        assert [e[4]["window"] for e in held] == [d[4]["window"]] * 2, d
    ext = stats["engine"]
    assert ext["pipeline_drains"] == len(drains)
    assert ext["windows"] - ext["windows_overlapped"] == len(drains)


def test_rid_stats_link_each_request_across_threads(served):
    handles, _, _, events = served
    for h in handles:
        submit = [e for e in events
                  if e[0] == "serve.submit" and e[4].get("rid") == h.rid]
        prefill = [e for e in events if e[0] == "serve.prefill"
                   and h.rid in _rids(e[4]["rids"])]
        deliver = [e for e in events
                   if e[0] == "serve.deliver" and e[4].get("rid") == h.rid]
        locks = [e for e in events
                 if e[0] == "serve.submit.lock" and e[4].get("rid") == h.rid]
        assert len(submit) == 1 and len(prefill) == 1, h.rid
        assert len(locks) == 1 and len(deliver) >= 2, h.rid
        assert all(submit[0][1] <= e[1] and e[2] <= submit[0][2]
                   for e in locks)
        # Three threads: the caller, the scheduler and the emitter.
        assert len({submit[0][3], prefill[0][3], deliver[0][3]}) == 3
        assert submit[0][1] < prefill[0][1] < deliver[0][1]


def test_lifecycle_stamps_are_ordered_for_every_request(served):
    handles, done, _, _ = served
    by_rid = {c.rid: c for c in done}
    for h in handles:
        stamps = [h.submitted_at, h.queued_at, h.admitted_at,
                  h.first_token_at, h.first_emitted_at, h.done_at]
        assert None not in stamps, h.rid
        assert stamps == sorted(stamps), (h.rid, stamps)
        assert 0.0 <= h.lock_wait_s <= h.queued_at - h.submitted_at
        c = by_rid[h.rid]
        assert c.ttft == pytest.approx(h.first_emitted_at - h.submitted_at)
        assert c.tpot == pytest.approx(
            (h.done_at - h.first_emitted_at) / (c.n_tokens - 1))


def test_frontend_counters_hold_one_wait_per_request(served):
    handles, _, stats, _ = served
    front = stats["engine"]["frontend"]
    assert front["submit_lock_s"] == [h.lock_wait_s for h in handles]
    assert sorted(front["admit_wait_s"]) == pytest.approx(sorted(
        h.admitted_at - h.queued_at for h in handles))
