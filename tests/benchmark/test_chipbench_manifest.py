"""BENCHMARK.json keeps to its contract, and a cell, a traffic mix and a
metric are found by name, so a later change adds them as files."""
import json
import re
import shutil
from pathlib import Path

import pytest

from benchmarks.chip import harness

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
FILE_PART = re.compile(r"^[A-Za-z0-9_.-]+$")
TOP = {"command", "paths", "run_seconds", "configs", "workloads",
       "end_to_end", "per_layer"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_and_paths():
    assert set(MANIFEST) == TOP
    assert 1 <= len(MANIFEST["paths"]) <= 16
    for p in MANIFEST["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p
        assert (ROOT / p).is_dir()
    cmd = MANIFEST["command"]
    assert 1 <= len(cmd) <= 32 and all(_line(w) for w in cmd)
    assert any(w.startswith(MANIFEST["paths"][0]) for w in cmd)
    for p in MANIFEST["paths"]:
        for f in (ROOT / p).rglob("*"):
            if "__pycache__" in f.parts:
                continue
            rel = f.relative_to(ROOT / p)
            assert all(FILE_PART.match(part) for part in rel.parts), rel


def test_run_seconds_fits_a_full_check():
    rs = MANIFEST["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_units_and_entries():
    seen = set()
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) \
            and _line(c["why"])
        assert len(c["reduced"]) <= 16 and all(NAME.match(k)
                                               for k in c["reduced"])
        assert c["file"].startswith(MANIFEST["paths"][0] + "/")
        assert json.loads((ROOT / c["file"]).read_text())["name"] == c["name"]
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4) and _line(w["why"])
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) <= max(
        1, len(MANIFEST["workloads"]) // 2)
    for m in MANIFEST["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in MANIFEST["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["source"] in SOURCES and _line(m["layer"])
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["name"] not in seen
        seen.add(m["name"])
    for group in ("configs", "workloads"):
        names = [x["name"] for x in MANIFEST[group]]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert len(json.dumps(MANIFEST)) <= 64 * 1024


def test_every_cell_reports_what_it_must():
    used = {w["config"] for w in MANIFEST["workloads"]}
    assert used == {c["name"] for c in MANIFEST["configs"]}
    for w in MANIFEST["workloads"]:
        cell = harness.load_cell(w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in e2e, (w["name"], m["name"])
            assert callable(harness.metric_reader(m["name"]))


@pytest.mark.parametrize("config", [c["name"] for c in MANIFEST["configs"]])
def test_config_file_is_what_the_program_runs(config):
    entry = next(c for c in MANIFEST["configs"] if c["name"] == config)
    spec = json.loads((ROOT / entry["file"]).read_text())
    assert spec["source"] == entry["source"]
    assert spec["reduced"] == entry["reduced"]
    harness.program_config(spec)          # raises on any difference
    bad = dict(spec, intermediate_size=spec["intermediate_size"] + 1)
    with pytest.raises(ValueError):
        harness.program_config(bad)


@pytest.mark.parametrize("config", [c["name"] for c in MANIFEST["configs"]])
def test_config_names_a_block_and_states_its_keys(config):
    """Every configuration names its block, a file under ``blocks/``, and
    states every key that block checks against the registry; a file that
    names no block, or a block that is not there, is an error."""
    entry = next(c for c in MANIFEST["configs"] if c["name"] == config)
    spec = json.loads((ROOT / entry["file"]).read_text())
    path = ROOT / MANIFEST["paths"][0] / "blocks" / f"{spec['block']}.py"
    assert path.is_file()
    block = harness.block_of(spec)
    assert set(block.REGISTRY_KEYS) <= set(spec)
    for name in ("REGISTRY_KEYS", "EXPERT_ROLES", "role", "layer_shapes",
                 "Reference", "prompt_flops", "decode_token_flops",
                 "paged_attn_need"):
        assert hasattr(block, name), name
    with pytest.raises(KeyError, match="names no block"):
        harness.program_config({k: v for k, v in spec.items()
                                if k != "block"})
    with pytest.raises(FileNotFoundError):
        harness.program_config(dict(spec, block="no_such_block"))
    with pytest.raises(KeyError):
        harness.program_config({k: v for k, v in spec.items()
                                if k != next(iter(block.REGISTRY_KEYS))})


def _alt_block(bench: Path) -> None:
    """``blocks/dense_alt.py``: the dense block under another name, with
    the role of the MLP's up projection renamed, so that its weights are
    drawn from other keys than the dense block's."""
    text = (bench / "blocks" / "dense.py").read_text()
    alt, n = re.subn(r'"up(?=[."])', '"lift', text)
    assert n >= 3      # the role table, the layer's shapes, the layer
    (bench / "blocks" / "dense_alt.py").write_text(alt)


def test_a_new_cell_and_metric_are_found_by_name(tmp_path):
    """Files and entries added beside the benchmark's are found without
    an edit to any file already there: a traffic mix, per-layer metrics,
    and a configuration with a block of its own, which the program's
    registry check, the weights and the check against the reference
    all take from its file."""
    import dataclasses
    import time

    import jax
    from chipbench_smoke import spec_of

    from repro.configs import smoke_config

    bench = tmp_path / MANIFEST["paths"][0]
    shutil.copytree(ROOT / MANIFEST["paths"][0], bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    _alt_block(bench)
    base = next(c for c in MANIFEST["configs"] if c["name"] == "yi-6b")
    alt_file = f"{MANIFEST['paths'][0]}/configs/yi-6b-alt.json"
    (tmp_path / alt_file).write_text(json.dumps(dict(
        json.loads((ROOT / base["file"]).read_text()), name="yi-6b-alt",
        block="dense_alt")))
    manifest = json.loads(json.dumps(MANIFEST))
    manifest["configs"].append(dict(base, name="yi-6b-alt", file=alt_file,
                                    why="a test"))
    manifest["workloads"].append(
        {"name": "dummy-cell", "config": "yi-6b-alt", "traffic": "dummy-mix",
         "chips": 1, "why": "a test"})
    manifest["per_layer"] += [
        {"name": "dummy_metric", "unit": "ms", "better": "lower",
         "source": "program_counter", "layer": "engine",
         "moves": "tpot_p95_ms", "workloads": ["dummy-cell"]},
        {"name": "dummy_silent", "unit": "ms", "better": "lower",
         "source": "program_counter", "layer": "engine",
         "moves": "tpot_p95_ms", "workloads": ["dummy-cell"]}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    mix = {"loop": "open", "rate_per_s": 8.0,
           "prompt_len": {"dist": "uniform", "min": 7, "max": 7},
           "output_len": {"dist": "uniform", "min": 5, "max": 5},
           "engine": {"max_slots": 2, "max_seq": 32, "page_size": 8},
           "check": {"requests": 2, "block": 2, "gap_limit": 0.5}}
    (bench / "traffic" / "dummy-mix.json").write_text(json.dumps(mix))
    (bench / "metrics" / "dummy_metric.py").write_text(
        "def read(ctx):\n    return 2.0 * ctx.slice_s\n")
    (bench / "metrics" / "dummy_silent.py").write_text(
        "def read(ctx):\n    return None\n")

    cell = harness.load_cell("dummy-cell", root=tmp_path)
    assert cell.traffic == mix and cell.chips == 1
    assert cell.spec["name"] == "yi-6b-alt"
    assert Path(cell.block.__file__) == bench / "blocks" / "dense_alt.py"
    assert "lift.w" in cell.block.layer_shapes(cell.spec, 0)
    harness.program_config(cell.spec, root=tmp_path)
    names = [m["name"] for m in cell.per_layer]
    assert "dummy_metric" in names and "prefill_rows_mean" not in names
    ctx = harness.LayerContext(
        spec=cell.spec, chips=1, tp=1, peaks=None, devices=[], slice_s=1.5,
        stats0={}, stats1={}, decode_contexts=[], prompt_lens=[],
        memory_peak_bytes=0, block=cell.block)
    layer_metrics = cell.per_layer
    cell.per_layer = [m for m in layer_metrics
                      if m["name"].startswith("dummy")]
    # A reader listed for the cell that finds nothing fails the run.
    with pytest.raises(RuntimeError, match="dummy_silent"):
        harness.per_layer(cell, ctx)
    cell.per_layer = cell.per_layer[:1]
    assert harness.per_layer(cell, ctx) == {
        "dummy_metric": {"value": 3.0, "unit": "ms"}}
    with pytest.raises(KeyError):
        harness.load_cell("no-such-cell", root=tmp_path)

    # The new block fills the program's weights and judges what it
    # served, at smoke width; the dense block's reference, whose up
    # projection is drawn from other keys, does not agree with it.
    cfg = dataclasses.replace(smoke_config("yi-6b"), param_dtype="bfloat16")
    cell.spec = dict(spec_of(cfg), block="dense_alt")
    line, sample, device = harness.serve(
        cell, 2**32 + 9, 1.0, False, cfg=cfg, devices=jax.devices(),
        process_t0=time.perf_counter(), compiles=harness.CompileCounter())
    assert line["failed"] == 0 and len(sample) == 2
    out = harness.check(cell, 2**32 + 9, line, sample, device)
    assert out["correct"], out["checks"]
    dense = dataclasses.replace(cell, block=harness.block_of(
        dict(cell.spec, block="dense"), tmp_path))
    out = harness.check(dense, 2**32 + 9, line, sample, device)
    assert not out["correct"], out["checks"]
