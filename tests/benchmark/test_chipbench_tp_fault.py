"""A tensor-parallel serve with its all-reduce left out fails the check.

On four CPU devices, a granite-20b-shaped smoke model (MQA, non-gated
GELU MLP, biases, tied embeddings; six layers, so that an error
compounds, and eight requests read) serves a closed loop over a (1, 4)
``model`` mesh through the benchmark's harness.  The sound serve passes
``harness.check`` under the ``yi6b-chat`` limit; the same serve with the
MLP's row-parallel down projection left without its all-reduce (each
chip's part taken for the sum) does not.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent

FAULT_RUN = """
import dataclasses, json, time
t0 = time.perf_counter()
import jax
from jax.sharding import PartitionSpec as P
from benchmarks.chip import harness
from chipbench_smoke import smoke_cell, spec_of
import repro.models.transformer as transformer
from repro.models.common import activation, linear_apply

cell, cfg = smoke_cell("granite-20b")
cfg = dataclasses.replace(cfg, n_heads=8, n_kv_heads=1, n_layers=6)
cell.spec = dict(spec_of(cfg), mesh={"data": 1, "model": 4})
cell.traffic = dict(cell.traffic, loop="closed", clients=6,
                    check=dict(cell.traffic["check"], requests=8))
cell.chips = 4
mesh = harness.make_mesh(cell.spec, jax.devices())


def run():
    return harness.run(cell, 99, 2.0, False, cfg=cfg, devices=jax.devices(),
                       process_t0=t0, compiles=harness.CompileCounter())


def mlp_without_allreduce(p, x, act, sharder=None):
    up = activation(act)(linear_apply(p["up"], x))
    lead = (None,) * (up.ndim - 1)
    part = jax.shard_map(lambda u, w: u @ w, mesh=mesh,
                         in_specs=(P(*lead, "model"), P("model", None)),
                         out_specs=P(), check_vma=False)
    return part(up, p["down"]["w"]) + p["down"]["b"]


sound = run()
transformer.mlp_apply = mlp_without_allreduce
broken = run()
print(json.dumps({"sound": sound, "broken": broken}))
"""


def test_a_tensor_parallel_serve_without_its_all_reduce_is_not_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(HERE.parents[1]), str(HERE.parents[1] / "src"),
                    str(HERE)]))
    out = subprocess.run([sys.executable, "-c", FAULT_RUN], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    sound, broken = got["sound"], got["broken"]
    assert sound["correct"], sound["checks"]
    assert sound["device"]["count"] == 4 and sound["failed"] == 0
    assert broken["failed"] == 0 and not broken["correct"]
    gap = broken["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]
