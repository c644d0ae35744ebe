"""Chip benchmark of the streaming ASR server: one run of one cell.

    python3 benchmarks/chip/run.py --workload edge8.bulk --seed 7 \
        --seconds 20 --trace 0

Everything is found by name from `BENCHMARK.json` at the root of the
checkout: the cell (`workloads`), its configuration (`configs[].file`,
whose `family` names `models/<family>.py` and the limits in
`limits/<family>.json`), its traffic mix (`traffic/<traffic>.json`,
whose `entry` names the module that drives it), the end-to-end and
per-layer readers (`end_to_end/<metric>.py`, `metrics/<metric>.py`) and
the kernels' operation and byte counts (`costs/<kernel>.py`).  A new
cell, mix or metric is new files.

A run: refuse anything but the TPU chips the cell asks for; make the
weights, tables and audio from the seed; build the engine and run every
step shape once (set-up); measure `--seconds` seconds; read the peak
device memory; free the engine; decode a sample of what the window
served with the plain reference and compare.  With `--trace 1` a span
of the window is traced and the per-layer metrics are read from it.
The last line of standard output is the result; the numbers compared
for `correct` are the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for _p in (HERE, HERE / "models"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))


def log(msg: str) -> None:
    print(msg, flush=True)


def load_module(path: Path):
    """Import a benchmark file by path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One cell of BENCHMARK.json with every file it names, loaded."""

    def __init__(self, root: Path, name: str):
        self.root = root
        self.chip = root / "benchmarks" / "chip"
        self.bench = json.loads((root / "BENCHMARK.json").read_text())
        found = [w for w in self.bench["workloads"] if w["name"] == name]
        if not found:
            raise SystemExit(f"no workload named {name!r}")
        self.workload = found[0]
        self.name = name
        entry = next(c for c in self.bench["configs"]
                     if c["name"] == self.workload["config"])
        self.cfg = json.loads((root / entry["file"]).read_text())
        self.mix = json.loads(
            (self.chip / "traffic" / f"{self.workload['traffic']}.json")
            .read_text())
        family = self.cfg["family"]
        self.limits = json.loads(
            (self.chip / "limits" / f"{family}.json").read_text())
        self.peaks = json.loads((self.chip / "peaks.json").read_text())

    def model(self):
        return load_module(self.chip / "models" / f"{self.cfg['family']}.py")

    def entry(self):
        return load_module(HERE / f"{self.mix['entry']}.py")

    def end_to_end(self) -> list:
        return [m for m in self.bench["end_to_end"]
                if self.name in m.get("workloads", [self.name])]

    def per_layer(self) -> list:
        mine = {m["name"] for m in self.end_to_end()}
        return [m for m in self.bench["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in mine)]


def require_chips(n: int) -> list:
    """The cell's TPU chips; anything else ends the run with no result."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        print(f"benchmark: needs {n} TPU chip(s), JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        raise SystemExit(3)
    return devs[:n]


def apply_precision(cfg: dict) -> None:
    """Run every thread's matmuls at the configuration's precision (a
    process-wide default: the server steps on its own worker thread)."""
    import jax

    if "matmul_precision" in cfg:
        jax.config.update("jax_default_matmul_precision",
                          cfg["matmul_precision"])


def watch_compiles() -> list:
    """(event, monotonic time) of every trace, backend compile and
    persistent-cache load from now on."""
    from jax import monitoring

    seen = []
    names = ("/jax/core/compile/jaxpr_trace_duration",
             "/jax/core/compile/backend_compile_duration")

    def on_duration(event, _secs, **kw):
        if event in names:
            seen.append((f"{event.rsplit('/', 1)[-1]} "
                         f"{kw.get('fun_name', '?')}", time.monotonic()))

    def on_event(event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            seen.append(("cache_load", time.monotonic()))

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)
    return seen


class RunView:
    """What a per-layer reader may read of one run."""

    def __init__(self, cell: Cell, measured: dict, reduced, peak: dict):
        self.sizes = cell.cfg["model"]
        self.dec = cell.cfg["decoder"]
        self.measured = measured
        self.trace = reduced
        self.peak = peak
        self._costs = cell.chip / "costs"

    def cost(self, name: str):
        return load_module(self._costs / f"{name}.py")


def end_to_end_values(cell: Cell, measured: dict, setup_s: float) -> dict:
    """The cell's end-to-end metrics, each read by
    `end_to_end/<name>.py` from what the window measured."""
    run = SimpleNamespace(measured=measured, setup_s=setup_s)
    return {m["name"]: {"value": load_module(
                cell.chip / "end_to_end" / f"{m['name']}.py").read(run),
            "unit": m["unit"]}
            for m in cell.end_to_end()}


def check_sample(measured: dict, n: int, seed: int) -> list:
    """Served results to compare: the longest, then others drawn from the
    seed, `n` in all."""
    import numpy as np

    served = sorted(measured["served"], key=lambda r: (-r["seconds"],
                                                       r["index"]))
    if len(served) <= n:
        return served
    rng = np.random.default_rng([int(seed) % 2 ** 63, 3])
    rest = rng.choice(len(served) - 1, n - 1, replace=False) + 1
    return [served[0]] + [served[i] for i in sorted(rest)]


def compare_with_reference(cell: Cell, model, system, sample: list,
                           seed: int) -> dict:
    from audio import utterance

    r = model.Reference(system)
    audios = [utterance(seed, s["index"], s["seconds"]) for s in sample]
    lps = r.log_probs(audios, cell.limits["reference_windows"],
                      cell.limits["sample"])
    beams = [r.decode(lp) for lp in lps]
    return model.compare([s["final"] for s in sample], beams)


def main(argv=None, root: Path = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = Cell(root, args.workload)
    entry = cell.entry()
    sys.path.insert(0, str(root / "src"))
    devs = require_chips(cell.workload["chips"])
    import jax
    from repro.runtime import compile_cache

    cache_dir = compile_cache.use_persistent_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    apply_precision(cell.cfg)
    compiles = watch_compiles()
    kind = devs[0].device_kind
    if kind not in cell.peaks:
        raise SystemExit(f"no peaks for device {kind!r} in peaks.json")
    peak = cell.peaks[kind]
    log(f"device {devs[0].platform} {kind} x{len(devs)}; compile cache "
        f"{cache_dir}")

    model = cell.model()
    t = time.monotonic()
    system = model.System(cell.cfg, args.seed)
    engine = model.build_engine(system)
    log(f"system and engine built in {time.monotonic() - t:.3f}s")
    model.warm_up(engine, log)

    tracer = None
    if args.trace:
        from devtrace import Tracer
        tracer = Tracer(str(root / ".bench_out" /
                            f"trace-{args.workload}-{args.seed}"))
    n_before = len(compiles)
    measured = entry.run(engine, cell.mix, args.seed, args.seconds, tracer,
                         log)
    setup_s = measured["w0"] - T_START
    in_window = [c for c in compiles[n_before:]
                 if measured["w0"] <= c[1] < measured["w0"]
                 + measured["window_s"]]
    log(f"compilations inside the window: {len(in_window)} "
        f"({', '.join(sorted({c[0] for c in in_window})) or 'none'}); "
        f"set-up {setup_s:.3f}s")
    after = [f"{c[0]} at {c[1] - measured['w0']:+.3f}s"
             for c in compiles[n_before:]]
    log(f"compilations after warm-up (time from window open): "
        f"{len(after)} {after[:12]}")
    stats = devs[0].memory_stats() or {}
    mem_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs) if stats else None
    log(f"memory peak {mem_peak} bytes of "
        f"{stats.get('bytes_limit')} on the fullest chip")
    del engine
    gc.collect()

    metrics = end_to_end_values(cell, measured, setup_s)
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": mem_peak}
    breakdown = None
    if args.trace:
        from devtrace import reduce_trace
        path = tracer.xplane()
        reduced = reduce_trace(path, tracer.window_s) if path else None
        shutil.rmtree(tracer.out_dir, ignore_errors=True)
        view = RunView(cell, measured, reduced, peak)
        metrics = {}
        for m in cell.per_layer():
            v = load_module(cell.chip / "metrics" / f"{m['name']}.py").read(
                view)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if reduced is not None:
            log(f"trace: {reduced.chips} chip(s) ran operations; kernel "
                f"calls by output shape {reduced.kernel_shapes()[:8]}")
        if reduced is not None and reduced.chips:
            device["busy_s"] = reduced.busy_s()
            device["window_s"] = reduced.window_s
            breakdown = {"device_ops": reduced.top_ops(10),
                         "idle_gaps": reduced.idle_gaps(10)}
    for name, m in metrics.items():
        log(f"metric {name} = {m['value']} {m['unit']}")

    sample = check_sample(measured, cell.limits["sample"], args.seed)
    t = time.monotonic()
    nums = compare_with_reference(cell, model, system, sample, args.seed)
    log(f"reference over {len(sample)} served results "
        f"({sum(s['seconds'] for s in sample):.3f} s of audio, longest "
        f"{max((s['seconds'] for s in sample), default=0):.3f} s) took "
        f"{time.monotonic() - t:.3f}s")
    limits = cell.limits["limits"]
    log("readings not compared: " + json.dumps(
        {k: v for k, v in nums.items() if k not in limits}))
    checks = {k: {"value": nums[k], "limit": lim}
              for k, lim in limits.items()}
    correct = (measured["failed"] == 0 and len(sample) > 0
               and all(c["value"] <= c["limit"] for c in checks.values()))
    result = {"correct": correct, "attempted": measured["attempted"],
              "failed": measured["failed"], "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for k, c in checks.items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
