"""Chip smoke: the serving main path at published width on TPU.

    python chip_smoke.py               # one chip: kernels + yi-6b serving
    python chip_smoke.py --four-chips  # granite-20b on a (1, 4) mesh only

One chip: the Pallas GEMM kernels at yi-6b decode shapes and
phi3.5-moe-42b expert widths against a float32 ``jnp.dot``, then
``yi-6b`` at its published widths served through the normal entry points
(``make_engine(kind="paged")`` -> ``ServeFrontend`` -> ``warmup`` ->
``submit`` -> ``drain``), then a teacher-forced ``forward_prefill`` over
each prompt plus its generated tokens on the same chip.  Four chips:
``granite-20b`` (multi-query attention, ~40 GB of bf16 weights) with its
parameters initialized straight into their tensor-parallel shards, the
same serve and the same reference on the same mesh, and a check that the
decode program never all-gathers the KV pool.  Weights are random, drawn
from ``--seed``.

Informational lines come first; the last line of standard output is
``{"ok": true, "device": {...}}``.  Any failed check raises, so the
script exits non-zero without that line; so does a run that finds no
TPU.  Everything runs in this one process, which is the only one that
may hold the chip.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import re
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# Teacher-forced reference: wherever the reference's top-2 logit margin
# exceeds this many logits (they are ~unit scale: a final RMSNorm into an
# lm_head drawn at 1/sqrt(d_model)), its argmax must be the engine's token;
# over all positions, near-ties included, at least MIN_AGREE of them must.
MARGIN_TOL = 0.25
MIN_AGREE = 0.9
# HBM that must stay free after weights and KV pool: the largest serving
# program (the 8 x 1024-token bucketed prefill) needs 1.2 GiB of
# temporaries and 0.5 GiB of output, from its compile for a described v5e.
PROGRAM_HEADROOM = 2 * 2**30
# Kernels vs float32 jnp.dot: max |err| over max |reference| (bf16 output
# rounding alone is 2**-9).
GEMM_TOL = 1e-2

ONE_CHIP = dict(arch="yi-6b", max_slots=8, max_seq=1152, page_size=128,
                prompt_lens=(16, 64, 130, 250, 300, 512, 700, 1024),
                new_tokens=(16, 32, 20, 24, 32, 16, 28, 32))
FOUR_CHIPS = dict(arch="granite-20b", max_slots=8, max_seq=640,
                  page_size=128, prompt_lens=(16, 100, 200, 300, 512),
                  new_tokens=(16, 32, 24, 20, 32))


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {msg}")


def gib(n: float) -> str:
    return f"{n / 2**30:.3f} GiB"


def peak_bytes(devices):
    return [d.memory_stats()["peak_bytes_in_use"] for d in devices]


def hbm_left(devices) -> int:
    """Least free HBM over ``devices``."""
    return min(s["bytes_limit"] - s["bytes_in_use"]
               for s in (d.memory_stats() for d in devices))


# --------------------------------------------------------------------------
# Kernel phase
# --------------------------------------------------------------------------
def _rel_err(out, ref) -> float:
    import jax.numpy as jnp
    out = jnp.asarray(out, jnp.float32)
    return float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))


def kernel_phase(seed: int) -> None:
    """``sisa_matmul(backend="pallas")`` at yi-6b decode shapes and
    ``flat_ragged_gemm`` at phi3.5-moe-42b expert widths vs f32 dots."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import flat_group_offsets, flat_ragged_gemm, sisa_matmul

    hi = jax.lax.Precision.HIGHEST
    key = jax.random.PRNGKey(seed)
    # (M, K, N): q/o, k/v, gate/up, down projections at 8 decode slots.
    for m, k, n in ((8, 4096, 4096), (8, 4096, 512), (8, 4096, 11008),
                    (8, 11008, 4096)):
        ka, kb, key = jax.random.split(key, 3)
        a = jax.random.normal(ka, (m, k), jnp.bfloat16)
        b = (jax.random.normal(kb, (k, n), jnp.float32)
             / np.sqrt(k)).astype(jnp.bfloat16)
        out = sisa_matmul(a, b, "pallas")
        ref = jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=hi)
        err = _rel_err(out, ref)
        log(f"[kernel] sisa_matmul pallas {m}x{k}x{n}: rel_err={err:.3e}")
        check(out.shape == (m, n) and err <= GEMM_TOL,
              f"sisa_matmul {m}x{k}x{n} rel_err {err} > {GEMM_TOL}")

    # phi3.5-moe-42b experts: 16 experts, d_model 4096, d_ff 6400; 512
    # tokens routed top-2 with a skewed expert load.
    g, d, f, rows = 16, 4096, 6400, 1024
    rng = np.random.default_rng(seed)
    load = rng.dirichlet(np.full(g, 0.5))
    sizes = rng.multinomial(rows, load).astype(np.int32)
    bm = 128
    offs = np.asarray(flat_group_offsets(jnp.asarray(sizes), bm))
    total = int(offs[-1]) + bm
    for din, dout in ((d, f), (f, d)):
        kx, kw, key = jax.random.split(key, 3)
        x = jax.random.normal(kx, (total, din), jnp.bfloat16)
        w = (jax.random.normal(kw, (g, din, dout), jnp.float32)
             / np.sqrt(din)).astype(jnp.bfloat16)
        out = flat_ragged_gemm(x, w, jnp.asarray(sizes), jnp.asarray(offs),
                               block_rows=bm, m_hint=bm)
        ref = jnp.zeros((total, dout), jnp.float32)
        for e in range(g):
            lo, hi_row = int(offs[e]), int(offs[e]) + int(sizes[e])
            if hi_row > lo:
                ref = ref.at[lo:hi_row].set(jnp.dot(
                    x[lo:hi_row].astype(jnp.float32),
                    w[e].astype(jnp.float32), precision=hi))
        err = _rel_err(out, ref)
        log(f"[kernel] flat_ragged_gemm {g}x{din}x{dout} rows={rows} "
            f"sizes={sizes.tolist()}: rel_err={err:.3e}")
        check(err <= GEMM_TOL,
              f"flat_ragged_gemm {din}->{dout} rel_err {err} > {GEMM_TOL}")
        del x, w, out, ref


# --------------------------------------------------------------------------
# Serving phase
# --------------------------------------------------------------------------
def serve_phase(cfg, params, spec, seed: int, mesh=None):
    """Serve ``spec``'s requests through make_engine -> ServeFrontend;
    returns (prompts, completions, decode HLO, engine info)."""
    import jax

    from repro.serve import EngineOptions, make_engine, ServeFrontend

    opts = EngineOptions(max_slots=spec["max_slots"], max_seq=spec["max_seq"],
                         page_size=spec["page_size"])
    eng = make_engine(cfg, params, kind="paged", options=opts, mesh=mesh)
    pool_bytes = eng.cache.resident_bytes()
    left = hbm_left(list(mesh.devices.flat) if mesh is not None
                    else jax.devices()[:1])
    pool_dtype = jax.tree.leaves(eng.cache.pools)[0].dtype
    log(f"[serve] {cfg.name}: paged pool {eng.num_pages} pages x "
        f"{spec['page_size']} tokens, kv_pool={eng.kv_quant or 'unquantized'}"
        f" {pool_dtype}, resident_bytes={pool_bytes} ({gib(pool_bytes)}); "
        f"HBM left per chip after weights and pool {gib(left)}; "
        f"rungs={eng.rungs}")
    check(left >= PROGRAM_HEADROOM,
          f"only {gib(left)} left for serving programs")
    fe = ServeFrontend(eng)
    t0 = time.perf_counter()
    fe.warmup(max_prompt_len=max(spec["prompt_lens"]))
    warm_s = time.perf_counter() - t0
    log(f"[serve] warmup (AOT compile of every prefill bucket and decode "
        f"rung, plus probe runs): {warm_s:.1f} s")
    hlo = eng.window_program_text(eng.rungs[-1])

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(2, cfg.vocab_size, size=n).astype(np.int32)
               for n in spec["prompt_lens"]]
    t0 = time.perf_counter()
    handles = [fe.submit(p, max_new_tokens=n)
               for p, n in zip(prompts, spec["new_tokens"])]
    done = fe.drain(timeout=900)
    serve_s = time.perf_counter() - t0
    stats = fe.stats
    metrics = fe.metrics()
    fe.shutdown()
    by_rid = {c.rid: c for c in done}
    comps = [by_rid.get(h.rid) for h in handles]
    n_ok = sum(c is not None and c.finish_reason == "length"
               and c.n_tokens == n
               for c, n in zip(comps, spec["new_tokens"]))
    log(f"[serve] completions: {n_ok}/{len(prompts)} finished 'length' "
        f"with their full budget; finish_reasons="
        f"{metrics['finish_reasons']}; {len(prompts)} requests in "
        f"{serve_s:.1f} s host time; coalesced prefill flushes="
        f"{metrics['coalesced_prefills']}; packed_prefills="
        f"{stats['packed_prefills']}; decode_compiles after warmup="
        f"{stats['decode_compiles']}; windows={stats['engine']['windows']}")
    check(n_ok == len(prompts), f"only {n_ok}/{len(prompts)} requests "
          f"completed with finish_reason 'length'")
    check(stats["decode_compiles"] == 0,
          f"decode_compiles={stats['decode_compiles']} after warmup")
    info = dict(warm_s=warm_s, num_pages=eng.num_pages,
                page_size=eng.page_size)
    del fe, eng
    return prompts, comps, hlo, info


# --------------------------------------------------------------------------
# Teacher-forced reference
# --------------------------------------------------------------------------
def reference_phase(cfg, params, prompts, comps, seq_len: int, mesh=None):
    """One causal ``forward_prefill`` per request over prompt + generated
    tokens (the bucketed prefill step the engine builds, same params,
    same devices); at every position whose top-2 margin exceeds
    ``MARGIN_TOL`` its argmax must be the token the engine emitted."""
    import jax
    import jax.numpy as jnp

    from repro.serve import make_bucketed_prefill_step

    k_max = max(c.n_tokens for c in comps)
    step = make_bucketed_prefill_step(cfg, mesh, batch_axes=())
    fn = jax.jit(lambda p, b: step(p, b)[0])
    toks0 = jnp.zeros((1, seq_len), jnp.int32)
    idx0 = jnp.zeros((1, k_max), jnp.int32)
    t0 = time.perf_counter()
    compiled = fn.lower(params, {"tokens": toks0,
                                 "last_index": idx0}).compile()
    compile_s = time.perf_counter() - t0
    n_pos = n_above = agree_above = agree_all = 0
    worst_disagree = None
    for prompt, comp in zip(prompts, comps):
        gen = np.asarray(comp.tokens, np.int32)
        seq = np.concatenate([prompt, gen[:-1]])
        toks = np.zeros((1, seq_len), np.int32)
        toks[0, :len(seq)] = seq
        n = len(gen)
        pos = len(prompt) - 1 + np.arange(k_max)
        pos = np.minimum(pos, len(seq) - 1)[None].astype(np.int32)
        logits = compiled(params, {"tokens": jnp.asarray(toks),
                                   "last_index": jnp.asarray(pos)})
        lg = np.asarray(logits[0, :n, :cfg.vocab_size], np.float32)
        top2 = np.sort(lg, axis=-1)[:, -2:]
        margin = top2[:, 1] - top2[:, 0]
        agree = lg.argmax(-1) == gen
        above = margin > MARGIN_TOL
        n_pos += n
        n_above += int(above.sum())
        agree_above += int((agree & above).sum())
        agree_all += int(agree.sum())
        bad = ~agree
        if bad.any():
            m = float(margin[bad].max())
            worst_disagree = max(m, worst_disagree or m)
    log(f"[reference] teacher-forced forward_prefill over prompt+generated "
        f"(compile {compile_s:.1f} s): argmax agrees at {agree_all}/{n_pos} "
        f"positions; {agree_above}/{n_above} positions with top-2 margin > "
        f"{MARGIN_TOL} agree; largest margin at a disagreement: "
        f"{worst_disagree}")
    check(agree_above == n_above,
          f"reference disagrees at {n_above - agree_above} positions with "
          f"margin > {MARGIN_TOL}")
    check(agree_all >= MIN_AGREE * n_pos,
          f"reference agrees at only {agree_all}/{n_pos} positions "
          f"(< {MIN_AGREE:.0%})")
    return compile_s


# --------------------------------------------------------------------------
# Runs
# --------------------------------------------------------------------------
def device_checks(hlo: str) -> None:
    from repro.kernels import resolve_paged_attn_backend
    from repro.kernels.runtime import force_interpret_enabled
    backend = resolve_paged_attn_backend()
    n_kernel = len(re.findall(r"paged_attn_\d+h_\d+p", hlo))
    log(f"[device] paged-attention backend={backend}; force-interpret="
        f"{force_interpret_enabled()}; decode program holds "
        f"{hlo.count('tpu_custom_call')} tpu_custom_call ops, paged_attn "
        f"kernel references={n_kernel}")
    check(backend == "pallas", f"paged-attention backend is {backend}")
    check(not force_interpret_enabled(), "force-interpret switch is on")
    check("tpu_custom_call" in hlo and n_kernel > 0,
          "decode program has no paged_attn Pallas custom call")


def run_one_chip(seed: int) -> int:
    import jax

    from repro.configs import get_config
    from repro.models import init_params

    dev = jax.devices()[0]
    kernel_phase(seed)
    spec = ONE_CHIP
    cfg = get_config(spec["arch"])
    log(f"[model] {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads}/{cfg.n_kv_heads} heads, head_dim "
        f"{cfg.resolved_head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
        f"{cfg.param_dtype}")
    t0 = time.perf_counter()
    params = jax.block_until_ready(jax.jit(functools.partial(
        init_params, cfg))(jax.random.PRNGKey(seed)))
    init_s = time.perf_counter() - t0
    w_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    stats = dev.memory_stats()
    log(f"[model] params {gib(w_bytes)} initialized under jit in "
        f"{init_s:.1f} s; bytes_in_use={stats['bytes_in_use']} "
        f"bytes_limit={stats['bytes_limit']} peak={stats['peak_bytes_in_use']}")
    prompts, comps, hlo, info = serve_phase(cfg, params, spec, seed)
    device_checks(hlo)
    ref_s = reference_phase(cfg, params, prompts, comps, spec["max_seq"])
    log(f"[timing] compile_s={info['warm_s'] + ref_s:.1f} (serving warmup "
        f"{info['warm_s']:.1f} + reference compile {ref_s:.1f}); "
        f"init_s={init_s:.1f}")
    log(f"[memory] peak_bytes_in_use={peak_bytes([dev])[0]} "
        f"({gib(peak_bytes([dev])[0])})")
    return 1


def run_four_chips(seed: int) -> int:
    import jax
    from jax.sharding import Mesh

    from repro.configs import get_config
    from repro.distributed.sharding import param_specs, to_named
    from repro.kernels.paged_attn import kv_pool_layout
    from repro.models import init_params

    devs = jax.devices()[:4]
    mesh = Mesh(np.asarray(devs).reshape(1, 4), ("data", "model"))
    spec = FOUR_CHIPS
    cfg = get_config(spec["arch"])
    log(f"[model] {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads}/{cfg.n_kv_heads} heads, d_ff {cfg.d_ff}, vocab "
        f"{cfg.vocab_size}, {cfg.param_dtype}; mesh {dict(mesh.shape)}; "
        f"pool layout {kv_pool_layout(cfg.n_heads, cfg.n_kv_heads, 4)}")
    key = jax.random.PRNGKey(seed)
    shapes = jax.eval_shape(lambda k: init_params(cfg, k), key)
    shardings = to_named(param_specs(shapes, cfg, mesh, fsdp=False), mesh)
    t0 = time.perf_counter()
    params = jax.block_until_ready(jax.jit(functools.partial(
        init_params, cfg), out_shardings=shardings)(key))
    init_s = time.perf_counter() - t0
    total = sum(x.nbytes for x in jax.tree.leaves(params))
    per_dev = {d.id: 0 for d in devs}
    for x in jax.tree.leaves(params):
        for sh in x.addressable_shards:
            per_dev[sh.device.id] += sh.data.nbytes
    log(f"[model] params {gib(total)} in total, per chip "
        f"{[gib(b) for b in per_dev.values()]}; initialized under jit into "
        f"their shards in {init_s:.1f} s")
    check(max(per_dev.values()) < total / 2,
          "a chip holds more than half of the model")
    prompts, comps, hlo, info = serve_phase(cfg, params, spec, seed,
                                            mesh=mesh)
    device_checks(hlo)
    pool_rows = f"{info['num_pages'] + 1},{info['page_size']},"
    gathers = [ln for ln in hlo.splitlines()
               if re.search(r"\ball-gather(-start)?\(", ln)]
    pool_gathers = [ln for ln in gathers if pool_rows in ln]
    log(f"[sharding] decode program: {len(gathers)} all-gather ops, "
        f"{len(pool_gathers)} on the KV pool (page rows {pool_rows[:-1]})")
    check(not pool_gathers, "decode program all-gathers the KV pool")
    ref_s = reference_phase(cfg, params, prompts, comps, spec["max_seq"],
                            mesh=mesh)
    log(f"[timing] compile_s={info['warm_s'] + ref_s:.1f} (serving warmup "
        f"{info['warm_s']:.1f} + reference compile {ref_s:.1f}); "
        f"init_s={init_s:.1f}")
    peaks = peak_bytes(devs)
    log(f"[memory] peak_bytes_in_use per chip={peaks} "
        f"({[gib(p) for p in peaks]})")
    check(max(peaks) < 16 * 2**30, "a chip peaked above 16 GiB")
    return len(devs)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="granite-20b sharded over a (1, 4) mesh, alone")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              "this script never falls back to the CPU", file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips, JAX found {len(devices)}",
              file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    dev = devices[0]
    log(f"[device] {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"jax {jax.__version__}; compile cache {cache_dir}")
    t0 = time.perf_counter()
    # The result line counts the chips the run used, not all it could see.
    used = (run_four_chips if args.four_chips else run_one_chip)(args.seed)
    log(f"[timing] total {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": used}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
