"""The harness at tiny sizes on the CPU: names, refusal, correctness checks
and the faults that must make `correct` false.

    JAX_PLATFORMS=cpu python -m pytest -q -p no:cacheprovider benchmarks/amr/tests
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

import bench
import reference as ref
import registry
import traffic

AMR = Path(__file__).resolve().parents[1]
ROOT = AMR.parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())

# each configuration cut to a size the CPU runs in seconds, on the jnp
# backend (Pallas would run interpreted); same classes, patterns and mixes
TINY = {
    "tet_fig12": {"base_level": 2, "fractal": {"types": [0, 3], "max_level": 4},
                  "backend": "jnp"},
    "hex_timings": {"base_level": 2, "fractal": {"child_ids": [0, 3, 5, 6],
                                                 "refine_below": 2, "max_level": 5},
                    "backend": "jnp"},
}
CELLS = [c["name"] for c in BENCH["workloads"]]
SEED = 2**31 + 12345  # seeds beyond 32 signed bits must work
FRACTAL = registry.load("patterns", "fractal")


def tiny_run(cell, seconds=0.3, fault=None, seed=SEED):
    return bench.run(cell, seed, seconds, trace=False, fault=fault,
                     require_tpu=False, config_override=TINY[cell.split(".")[0]],
                     log=lambda *a: None)


def config_of(name):
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    return json.loads((ROOT / entry["file"]).read_text())


def _cli(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run(
        [sys.executable, "benchmarks/amr/bench.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(out: str) -> bool:
    return not any(ln.startswith("{") for ln in out.splitlines())


def test_refuses_without_tpu():
    p = _cli(ROOT)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "no TPU" in p.stderr


def test_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(AMR, tmp_path / "benchmarks" / "amr",
                    ignore=shutil.ignore_patterns("__pycache__", "_trace"))
    p = _cli(tmp_path)
    assert p.returncode != 0
    assert _no_result(p.stdout)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_finds_its_files_by_name(cell):
    c, config, mix, e2e, per_layer = bench.load_cell(cell)
    assert cell == f"{c['config']}.{c['traffic']}"
    assert config["name"] == c["config"] and mix["pattern"]
    names = {m["name"] for m in e2e}
    assert {"setup_s", "cycle_s"} <= names
    assert per_layer
    ctx = SimpleNamespace(cycles=[{"adapt_s": 1.0, "balance_s": 2.0, "ghost_s": 0.5,
                                   "cycle_s": 3.5}] * 2,
                          window_s=7.0, setup_s=9.0, trace=None,
                          counters={"compiles": 0, "dispatches": 40, "host_fetches": 8})
    for m in e2e + per_layer:
        v = bench.reader(m["name"])(ctx)
        assert v is None or np.isfinite(v)


def test_workloads_filter_and_unknown_names(tmp_path):
    doc = dict(BENCH)
    doc["workloads"] = BENCH["workloads"] + [
        {"name": "hex_timings.band", "config": "hex_timings", "traffic": "band",
         "chips": 1, "why": "a later cell, found by its names alone"}]
    doc["per_layer"] = BENCH["per_layer"] + [
        {"name": "adapt_s", "unit": "s/cycle", "better": "lower", "source": "host_clock",
         "layer": "forest drivers", "moves": "cycle_s", "workloads": [CELLS[0]]}]
    f = tmp_path / "BENCHMARK.json"
    f.write_text(json.dumps(doc))
    cell, config, mix, _, per_layer = bench.load_cell("hex_timings.band", f)
    assert config["eclass"] == "hex" and mix["pattern"] == "band"
    assert sum(m["name"] == "adapt_s" for m in per_layer) == 1
    with pytest.raises(bench.BenchError):
        bench.load_cell("no_such.cell", f)
    with pytest.raises(bench.BenchError):
        bench.reader("no_such_metric")


# A later cell that brings a coarse mesh, a traffic pattern, a mix and a
# configuration of its own, as new files and new BENCHMARK.json entries.
NEW_FILES = {
    "meshes/open_cube.py": """
import reference as ref


def program(config):
    from repro.core.cmesh import cmesh_hex_brick
    return cmesh_hex_brick(3, (1, 1, 1), periodic=(False, False, False))


def reference(config):
    return ref.Mesh(ref.HEX, 1)
""",
    "patterns/refine_once.py": """
import numpy as np
import reference as ref


class Pattern:
    def __init__(self, gen):
        self.top = gen.base_level + int(gen.mix["levels"])

    def flags(self, k, tree, level, anchor, stype):
        return (level < self.top).astype(np.int32)

    def expected_adapt(self, mesh, k, base):
        return ref.refine_while(mesh, base, lambda x: x.level < self.top)
""",
    "traffic/refine_once.json": json.dumps(
        {"pattern": "refine_once", "levels": 1, "carry": False, "warmup_cycles": 1}),
    "configs/open_cube.json": json.dumps(
        {"name": "open_cube", "eclass": "hex", "dim": 3, "trees": 1,
         "coarse_mesh": "open_cube", "base_level": 2, "ranks": 1, "backend": "jnp"}),
}


def test_a_new_cell_needs_only_new_files(tmp_path):
    shutil.copytree(AMR, tmp_path / "benchmarks" / "amr",
                    ignore=shutil.ignore_patterns("__pycache__", "_trace"))
    before = {p: p.read_bytes() for p in (tmp_path / "benchmarks").rglob("*.py")}
    for rel, text in NEW_FILES.items():
        path = tmp_path / "benchmarks" / "amr" / rel
        assert not path.exists()
        path.write_text(text)
    doc = dict(BENCH)
    doc["configs"] = BENCH["configs"] + [
        {"name": "open_cube", "source": "a test", "file":
         "benchmarks/amr/configs/open_cube.json", "reduced": [], "why": "a test"}]
    doc["workloads"] = BENCH["workloads"] + [
        {"name": "open_cube.refine_once", "config": "open_cube",
         "traffic": "refine_once", "chips": 1, "why": "a test"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    code = ("import sys; sys.path.insert(0, 'benchmarks/amr'); import bench; "
            "r = bench.run('open_cube.refine_once', 5, 0.2, trace=False, "
            "require_tpu=False, log=lambda *a: None); "
            "print(r['correct'], r['attempted'], r['checks']['adapt_diff']['value'])")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join([str(ROOT / "src"),
                                          os.environ.get("PYTHONPATH", "")])}
    p = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    correct, attempted, diff = p.stdout.split()[-3:]
    assert correct == "True" and int(attempted) >= 1 and diff == "0"
    assert all(p.read_bytes() == b for p, b in before.items())


def test_peaks_are_keyed_by_device_kind():
    v5e = bench.peaks_for("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["bf16_flops_per_s"] == 197e12
    with pytest.raises(bench.BenchError):
        bench.peaks_for("cpu")


@pytest.mark.parametrize("name", ["tet_fig12", "hex_timings"])
def test_analytic_counts_match_the_configs(name):
    cfg = config_of(name)
    assert FRACTAL.analytic_count(cfg) == cfg["leaves"]["after_adapt"]


@pytest.mark.parametrize("name,seed", [("tet_fig12", 0), ("hex_timings", 0),
                                       ("hex_timings", 5)])
def test_traffic_gives_the_analytic_count_and_the_programs_leaves(name, seed):
    from repro.core import forest as F
    from repro.core.batch import use_backend

    cfg = {**config_of(name), **TINY[name]}
    gen = traffic.Traffic(cfg, {"pattern": "fractal", "carry": False,
                                "warmup_cycles": 1}, seed)
    want = gen.expected_adapt(0)
    assert len(want) == FRACTAL.analytic_count(cfg)
    pipe = bench.Pipeline(cfg, gen, None)
    with use_backend("jnp"):
        got = [F.adapt(f, gen.callback(0), recursive=True) for f in pipe.new()]
    assert ref.compare(gen.mesh, bench.as_leaves(got), want) == (0, 0)


def test_band_keeps_2to1_without_balance():
    from repro.core import forest as F
    from repro.core.batch import use_backend

    cfg = {**config_of("tet_fig12"), **TINY["tet_fig12"], "base_level": 3}
    mix = json.loads((AMR / "traffic" / "band.json").read_text())
    gen = traffic.Traffic(cfg, mix, SEED)
    pipe = bench.Pipeline(cfg, gen, None)
    with use_backend("jnp"):
        state = pipe.new()
        for k in range(gen.pattern.positions + 2):
            state = [F.adapt(f, gen.callback(k), recursive=True) for f in state]
            got = bench.as_leaves(state)
            want = gen.expected_adapt(k)
            assert ref.compare(gen.mesh, got, want) == (0, 0)
            assert len(ref.balance(gen.mesh, want)) == len(want)
            assert set(np.unique(got.level)) == {3, 4}


def test_band_seeds_keep_the_work():
    cfg = {**config_of("tet_fig12"), **TINY["tet_fig12"], "base_level": 3}
    mix = json.loads((AMR / "traffic" / "band.json").read_text())
    a, b = traffic.Traffic(cfg, mix, 1), traffic.Traffic(cfg, mix, SEED)
    for k in range(4):
        xa, xb = a.expected_adapt(k), b.expected_adapt(k)
        assert len(xa) == len(xb)
        assert not np.array_equal(np.sort(ref.keys(xa)), np.sort(ref.keys(xb)))


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    r = tiny_run(cell)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 1
    assert list(r)[-1] == "checks"
    assert all(c["value"] == 0 for c in r["checks"].values())
    assert set(r["metrics"]) == {"cycle_s", "setup_s"}


def test_traced_run_records_the_window_start(monkeypatch):
    import functools

    import trace_reduce

    # XLA:CPU runs the ops on host threads: stand them in for a device
    monkeypatch.setattr(bench.tracing, "reduce", functools.partial(
        trace_reduce.reduce, device_prefix="/host:CPU",
        op_line=lambda n: n.startswith("tf_XLA"), module_line=lambda n: False))
    monkeypatch.setattr(bench, "TRACE_SECONDS", 0.2)
    cell = "tet_fig12.band"
    r = bench.run(cell, SEED, 0.6, trace=True, require_tpu=False,
                  config_override=TINY["tet_fig12"], log=lambda *a: None)
    assert r["correct"] and list(r)[-1] == "checks"
    names = {m["name"] for m in bench.load_cell(cell)[4]}
    assert set(r["metrics"]) == names - {"pallas_device_s_per_cycle"}  # jnp: no kernel
    assert 0.2 <= r["device"]["window_s"] < 0.6 and 0 < r["device"]["busy_s"]
    assert r["breakdown"]["idle_gaps"]


# A step that returns its state unchanged (Balance in the fractal cells,
# Adapt in the band cell, where Balance rightly changes nothing), half the
# batch left out, one answer altered where it is produced.  At one rank
# there is no exchange between chips to leave out.
FAULTS = [(c, f) for c in CELLS for f in (
    ("adapt_unchanged" if c.endswith(".band") else "balance_unchanged"),
    "drop_half", "alter_one")]


@pytest.mark.parametrize("cell,fault", FAULTS)
def test_fault_makes_correct_false(cell, fault):
    r = tiny_run(cell, fault=fault)
    assert r["correct"] is False and r["failed"] >= 1
    assert any(c["value"] > c["limit"] for c in r["checks"].values())
