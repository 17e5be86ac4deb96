#!/usr/bin/env python3
"""AMR cycle benchmark: one cell of `BENCHMARK.json` on this machine's chips.

    python3 benchmarks/amr/bench.py --workload tet_fig12.fractal --seed 7 \
        --seconds 40 --trace 0

A cell is `<config>.<traffic>`.  The harness finds everything by name:
the configuration in the file that `BENCHMARK.json` gives it
(`configs/<config>.json`) and its coarse mesh (`meshes/<coarse_mesh>.py`),
the traffic mix in `traffic/<traffic>.json` (read by the one generator,
`traffic.py`) and the pattern it follows (`patterns/<pattern>.py`), and
each metric's reader in `metrics/<metric>.py`.

One run:

1. Set-up: the persistent compile cache at its fixed place in the
   checkout, a TPU check (no fallback), the base forest from
   `repro.core.forest.new_uniform`, and the mix's warm-up cycles, which
   compile (or load from the cache) exactly the shapes of the cell.
   `setup_s` ends here.
2. Window: AMR cycles back to back, `adapt` -> `balance` -> `ghost` at one
   rank on `LocalComm` under the configuration's backend, until
   `--seconds` have passed; every started cycle finishes and counts.
   With `--trace 1` the profiler records the window's first
   `TRACE_SECONDS`, and the device's numbers are those of that part.
3. Check: after the window, the outputs of the window's cycles are
   compared with the plain reference (`reference.py`), and every number
   compared is printed beside its limit.
4. Output: one JSON line, last on stdout.

Without a TPU, or with fewer chips than the cell asks for, it exits 1 and
prints no result.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import hashlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
TRACE_DIR = HERE / "_trace"
for _p in (str(HERE), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import reference as ref  # noqa: E402
import registry  # noqa: E402
import trace_reduce as tracing  # noqa: E402
import traffic  # noqa: E402
from registry import BenchError  # noqa: E402

PHASES = ("adapt", "balance", "ghost")
# Faults planted under the timed path, for the tests and the control runs:
# each must make `correct` false in the cells where it can occur.
FAULTS = ("adapt_unchanged", "balance_unchanged", "drop_half", "alter_one")
# The profiler records the first this many seconds of a `--trace 1` window:
# stopping a trace of the whole 30 s window and reading it took 8 s on a
# v5e host, which put a traced run at the end of its time allowance.
TRACE_SECONDS = 10.0


# ------------------------------------------------------------------ the cell
def load_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise BenchError(f"missing {path}") from None


def load_cell(workload: str, bench_file: Path = ROOT / "BENCHMARK.json"):
    """(cell, config, mix, metric entries) of `workload`: the end-to-end
    metrics, or with `per_layer` the per-layer ones, that the cell reports
    (a metric with a `workloads` list is reported only in those)."""
    bench = load_json(bench_file)
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in {bench_file.name}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(ROOT / configs[cell["config"]]["file"])
    mix = load_json(HERE / "traffic" / f"{cell['traffic']}.json")

    def mine(entries):
        return [m for m in entries if workload in m.get("workloads", [workload])]

    return cell, config, mix, mine(bench["end_to_end"]), mine(bench["per_layer"])


def reader(metric: str):
    """The `read(ctx)` function of `metrics/<metric>.py`."""
    return registry.load("metrics", metric).read


# ------------------------------------------------------------------- digests
def leaf_digest(f) -> str:
    """sha256 of one rank's packed leaves: (tree, anchor, level, stype)."""
    h = hashlib.sha256()
    for a in (f.tree, f.anchor, f.level, f.stype):
        h.update(np.ascontiguousarray(a, np.int32).tobytes())
    return h.hexdigest()


def ghost_digest(g: dict) -> str:
    """sha256 of one rank's packed ghost layer."""
    h = hashlib.sha256()
    for k in ("tree", "anchor", "level", "stype", "owner"):
        h.update(np.ascontiguousarray(g[k], np.int32).tobytes())
    return h.hexdigest()


def as_leaves(forests) -> ref.Leaves:
    """The leaves of the local forests, rank after rank, for the reference."""
    return ref.Leaves.of(*(np.concatenate([getattr(f, k) for f in forests])
                           for k in ("tree", "level", "anchor", "stype")))


# ------------------------------------------------------------------ pipeline
class Pipeline:
    """The system under test: the public forest entry points at one rank."""

    def __init__(self, config: dict, gen: traffic.Traffic, fault: str | None):
        from repro.core import forest as F

        if int(config["ranks"]) != 1:
            raise BenchError("this harness drives one rank (P=1)")
        self.F, self.gen, self.fault = F, gen, fault
        self.comm = F.LocalComm()
        self.cmesh = gen.coarse.program(config)
        self.config = config

    def new(self):
        c = self.config
        return self.F.new_uniform(int(c["dim"]), int(c["trees"]),
                                  int(c["base_level"]), self.comm, cmesh=self.cmesh)

    def cycle(self, state, k: int, sync):
        """One AMR cycle from `state`; returns (adapted, balanced, ghosts,
        {phase: seconds})."""
        import jax

        F, fault, secs = self.F, self.fault, {}
        cb = self.gen.callback(k)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("adapt"):
            a = [F.adapt(f, cb, recursive=True) for f in state]
            if fault == "adapt_unchanged":
                a = list(state)
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("balance"):
            b = F.balance(a, self.comm)
            if fault == "balance_unchanged":
                b = a
            elif fault == "drop_half":
                b = [f.replace_elements(f.anchor[: f.num_local // 2],
                                        f.level[: f.num_local // 2],
                                        f.stype[: f.num_local // 2],
                                        f.tree[: f.num_local // 2]) for f in b]
            elif fault == "alter_one":
                f = b[0]
                lvl = f.level.copy()
                lvl[f.num_local // 2] += 1
                b = [f.replace_elements(f.anchor, lvl, f.stype, f.tree)] + b[1:]
        t2 = time.perf_counter()
        with jax.profiler.TraceAnnotation("ghost"):
            g = F.ghost(b, self.comm)
        sync()
        t3 = time.perf_counter()
        secs.update(adapt_s=t1 - t0, balance_s=t2 - t1, ghost_s=t3 - t2,
                    cycle_s=t3 - t0)
        return a, b, g, secs


# --------------------------------------------------------------- correctness
def check(gen: traffic.Traffic, outputs: list, seed: int) -> tuple[dict, int]:
    """Compare the window's outputs with the reference.

    Static mixes expect the same mesh in every cycle: every distinct output
    (by digest) is compared.  Carried mixes expect another mesh in every
    cycle: a sample of cycles drawn from the seed, and the last, are.
    Returns ({name: {value, limit}}, cycles found wrong)."""
    mesh = gen.mesh
    worst = dict.fromkeys(("adapt_diff", "adapt_order", "balance_diff",
                           "balance_order", "ghost_diff"), 0)
    if gen.carry:
        rng = np.random.default_rng(seed)
        pick = set(rng.choice(len(outputs), size=min(3, len(outputs)),
                              replace=False).tolist()) | {len(outputs) - 1}
        groups = [[i] for i in sorted(pick)]
    else:
        by_digest = {}
        for i, (_, a, b, g) in enumerate(outputs):
            key = (tuple(leaf_digest(f) for f in a), tuple(leaf_digest(f) for f in b),
                   tuple(ghost_digest(x) for x in g))
            by_digest.setdefault(key, []).append(i)
        groups = list(by_digest.values())
    base = gen.base()
    expected = {}
    wrong = 0
    for group in groups:
        k, a, b, g = outputs[group[0]]
        ek = k if gen.carry else 0
        if ek not in expected:
            want_a = gen.expected_adapt(ek, base)
            expected[ek] = (want_a, ref.balance(mesh, want_a))
        want_a, want_b = expected[ek]
        got = {}
        got["adapt_diff"], got["adapt_order"] = ref.compare(mesh, as_leaves(a), want_a)
        got["balance_diff"], got["balance_order"] = ref.compare(mesh, as_leaves(b), want_b)
        # one rank: no face neighbour is remote, so the ghost layer is empty
        got["ghost_diff"] = int(sum(len(x["level"]) for x in g))
        if any(got.values()):
            wrong += len(group)
        for name, v in got.items():
            worst[name] = max(worst[name], int(v))
    return {n: {"value": v, "limit": 0} for n, v in worst.items()}, wrong


# ----------------------------------------------------------------------- run
def devices(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX runs on {devs[0].platform!r}")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs


def peaks_for(device_kind: str) -> dict:
    """The chip's published peaks; a kind missing from the table is an error."""
    table = load_json(HERE / "peaks.json")["devices"]
    if device_kind not in table:
        raise BenchError(f"no peaks for {device_kind!r} in peaks.json")
    return table[device_kind]


def memory_peak(devs) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run(workload: str, seed: int, seconds: float, trace: bool,
        fault: str | None = None, require_tpu: bool = True,
        config_override: dict | None = None, log=print) -> dict:
    """One run of `workload`; returns the result line as a dict."""
    cell, config, mix, e2e, per_layer = load_cell(workload)
    config = {**config, **(config_override or {})}
    from repro.launch.compile_cache import compile_counter, enable_compile_cache

    cache = enable_compile_cache()
    import jax

    # every program goes to the cache, so only a checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = devices(int(cell["chips"]), require_tpu)
    if require_tpu:
        peaks_for(devs[0].device_kind)
    from repro.core import batch

    gen = traffic.Traffic(config, mix, seed)
    pipe = Pipeline(config, gen, fault)
    sync = lambda: jax.block_until_ready(jax.device_put(np.int32(0)) + 1)  # noqa: E731
    counter = compile_counter()
    log(f"{workload}: seed {seed}, {devs[0].device_kind} x {len(devs)}, "
        f"backend {config['backend']}, compile cache {cache}")
    with batch.use_backend(config["backend"]):
        state = base = pipe.new()
        log(f"base forest built {time.perf_counter() - _T0:.6f} s after start")
        for k in range(gen.warmup_cycles):
            c0, h0, s0 = counter["compiles"], counter["cache_hits"], counter["compile_s"]
            _, b, _, secs = pipe.cycle(state, k, sync)
            state = b if gen.carry else base
            log(f"warm-up cycle {k}: {secs['cycle_s']:.6f} s, "
                f"{counter['compiles'] - c0} compiles ({counter['cache_hits'] - h0} "
                f"from the cache, {counter['compile_s'] - s0:.6f} s)")
        setup_s = time.perf_counter() - _T0
        k = gen.warmup_cycles
        if trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        batch.reset_dispatch_counts()
        batch.reset_host_fetch_counts()
        c0 = counter["compiles"]
        outputs, cycles = [], []

        def cycles_until(t_end):
            """Run cycles until `t_end` seconds of the window have passed;
            the cycle under way finishes and counts."""
            nonlocal state, k
            while True:
                a, b, g, secs = pipe.cycle(state, k, sync)
                outputs.append((k, a, b, g))
                cycles.append(secs)
                state = b if gen.carry else base
                k += 1
                if time.perf_counter() - w0 >= t_end:
                    return

        w0 = time.perf_counter()
        if trace:
            with jax.profiler.TraceAnnotation("window"):
                cycles_until(min(seconds, TRACE_SECONDS))
            traced = len(cycles)
            t = time.perf_counter()
            jax.profiler.stop_trace()
            log(f"traced {traced} cycles; trace stopped in "
                f"{time.perf_counter() - t:.6f} s")
        if time.perf_counter() - w0 < seconds:
            cycles_until(seconds)
        window_s = time.perf_counter() - w0
        counters = {"compiles": counter["compiles"] - c0,
                    "dispatches": sum(batch.dispatch_counts().values()),
                    "host_fetches": sum(batch.host_fetch_counts().values())}
    peak = memory_peak(devs)
    del state, base
    log(f"window: {len(cycles)} cycles in {window_s:.6f} s, "
        f"{counters['compiles']} compiles; cycles (s): "
        + " ".join(f"{c['cycle_s']:.4f}" for c in cycles))
    reduced = None
    if trace:
        t = time.perf_counter()
        reduced = tracing.reduce(tracing.load(str(TRACE_DIR)), "window", PHASES)
        reduced["cycles"] = traced
        log(f"trace reduction: {time.perf_counter() - t:.6f} s")
    t = time.perf_counter()
    checks, wrong = check(gen, outputs, seed)
    log(f"reference check: {time.perf_counter() - t:.6f} s")
    ctx = SimpleNamespace(cycles=cycles, window_s=window_s, setup_s=setup_s,
                          counters=counters, trace=reduced)
    metrics = {}
    for m in (per_layer if trace else e2e):
        v = reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": wrong == 0 and all(c["value"] <= c["limit"]
                                            for c in checks.values()),
              "attempted": len(cycles), "failed": wrong, "metrics": metrics,
              "device": device}
    if reduced is not None:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", choices=FAULTS, default=None,
                    help="plant a fault under the timed path (control runs)")
    args = ap.parse_args(argv)
    log = lambda *a: print(*a, file=sys.stderr, flush=True)  # noqa: E731
    try:
        result = run(args.workload, args.seed, args.seconds, bool(args.trace),
                     fault=args.fault, log=log)
    except BenchError as e:
        log(f"bench: {e}")
        return 1
    log(f"run: {time.perf_counter() - _T0:.6f} s from start to result")
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
