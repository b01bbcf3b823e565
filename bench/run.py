#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``workloads`` in ``BENCHMARK.json``) names a configuration
(its ``file``), a traffic mix (``bench/traffic/<name>.json``, whose
``driver`` key names the general driver in ``bench/drivers/``) and the
chips it needs; its limits are in ``bench/limits/<cell>.json`` and each
metric is read by ``bench/metrics/<metric>.py``.  A new cell, mix or metric
is new files and entries, not an edit.

A run: set-up (imports, data from ``--seed``, warm-up of every program the
window runs, all counted in ``setup_s``), the measured window of
``--seconds``, then the check against the plain reference (not counted).
With ``--trace 1`` the window is traced and the line carries the per-layer
metrics; with ``--trace 0`` the end-to-end metrics.  The last lines on
standard error are each compared number beside its limit; the last line on
standard output is the JSON result.  Without a TPU, or with fewer chips
than the cell asks for, it exits 1 and prints no result.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = ROOT / "bench_traces"
CACHE_DIR = ROOT / ".jax_cache"


class Refused(RuntimeError):
    pass


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _find(kind: str, name: str, suffix: str, dirs) -> Path:
    for d in dirs:
        p = Path(d) / kind / f"{name}{suffix}"
        if p.is_file():
            return p
    raise Refused(f"no {kind}/{name}{suffix} under {[str(d) for d in dirs]}")


def _applies(metric: dict, cell: str, reported: set) -> bool:
    """A per-layer metric is read in the cells it lists, or, without a
    list, in every cell that reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric["moves"] in reported


def plan(spec: dict, cell_name: str, dirs) -> dict:
    """Everything a run of ``cell_name`` needs, found by name."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if cell_name not in cells:
        raise Refused(f"no workload {cell_name!r} (have {sorted(cells)})")
    cell = cells[cell_name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or cell_name in m["workloads"]]
    reported = {m["name"] for m in e2e}
    return dict(
        cell=cell,
        config=json.loads((ROOT / cfg_entry["file"]).read_text()),
        traffic=json.loads(_find("traffic", cell["traffic"], ".json", dirs).read_text()),
        limits=json.loads(_find("limits", cell_name, ".json", dirs).read_text()),
        end_to_end=e2e,
        per_layer=[m for m in spec["per_layer"] if _applies(m, cell_name, reported)],
    )


class Bench:
    """What the driver and the metric readers share in one run."""

    def __init__(self, cell, config, traffic, seed, spans, counter):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.chips, self.seed = cell["chips"], seed
        self.spans, self.counter = spans, counter
        self.notes: dict = {}

    def note(self, **fields) -> None:
        """Facts printed before the result line (e.g. the kernel route)."""
        self.notes.update(fields)
        print(json.dumps({"note": fields}), file=sys.stderr, flush=True)


class Readings:
    """The facts per-layer and end-to-end metric readers take their numbers
    from: ``setup_s``, ``window_s``, ``rounds``, ``chips``, ``peak``,
    ``compiles_in_window``, ``spans``, ``trace`` (a ``TraceSummary`` or
    None), ``driver`` (round work, screening calls, experiments)."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def window_spans(self, name: str) -> list[float]:
        """Durations of the benchmark's ``name`` spans inside the window."""
        (_, lo, hi), = [r for r in self.spans.records if r[0] == "window"]
        return [e - s for n, s, e in self.spans.records
                if n == name and s >= lo and e <= hi]


def _import_repo():
    src = ROOT / "src"
    if not (src / "repro" / "fed" / "api.py").is_file():
        raise Refused(f"no repro package under {src}")
    sys.path.insert(0, str(src))
    import repro.fed.api

    if not Path(repro.fed.api.__file__).resolve().is_relative_to(src):
        raise Refused(f"imported repro from {repro.fed.api.__file__}, not {src}")


def run_cell(args, *, dirs=(BENCH,), spec=None, require_tpu=True,
             trace_dir=TRACE_DIR, t_start=None, compile_cache=True) -> dict:
    """One run of one cell; returns the result dict (``checks`` last)."""
    spec = spec or json.loads((ROOT / "BENCHMARK.json").read_text())
    p = plan(spec, args.workload, dirs)
    cell = p["cell"]

    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise Refused(f"no TPU found (JAX platform is {devices[0].platform!r}); "
                      "this benchmark runs on the chip only")
    if len(devices) < cell["chips"]:
        raise Refused(f"{args.workload} needs {cell['chips']} chips, found {len(devices)}")
    _import_repo()
    from bench import peaks, trace
    from bench.check import judge
    from bench.spans import CompileCounter, Spans

    cache_dir = None
    if compile_cache:
        # A fixed directory in the checkout, whatever the environment says:
        # the program's use_compile_cache() takes it from the variable.
        cache_dir = str(CACHE_DIR)
        os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    kind = devices[0].device_kind
    peak = peaks.peak_of(kind) if require_tpu else peaks.PEAKS["TPU v5 lite"]
    spans, counter = Spans(), CompileCounter()
    bench = Bench(cell, p["config"], p["traffic"], args.seed, spans, counter)
    bench.note(device_kind=kind, platform=devices[0].platform, devices=len(devices),
               compile_cache=cache_dir, jax=jax.__version__)
    driver = _load(BENCH / "drivers" / f"{p['traffic']['driver']}.py",
                   f"bench.drivers.{p['traffic']['driver']}").Driver(bench)

    driver.setup()
    counter.mark()
    setup_s = time.perf_counter() - (T_START if t_start is None else t_start)
    bench.note(setup_compiles=counter.n, setup_compile_s=counter.seconds)
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    with spans.span("window"):
        driver.window(args.seconds)
    if args.trace:
        jax.profiler.stop_trace()
    compiles = counter.since_mark()
    used = devices[:cell["chips"]]
    stats = [d.memory_stats() or {} for d in used]
    mem_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)

    driver.release()
    values = driver.check()
    bench.note(compared=values)
    correct, checks = judge(values, p["limits"])

    summary = None
    if args.trace:
        summary = trace.reduce_trace(trace.newest_xplane(str(trace_dir)))
    readings = Readings(
        setup_s=setup_s, window_s=driver.window_s, rounds=driver.rounds,
        chips=cell["chips"], peak=peak, compiles_in_window=compiles, spans=spans,
        trace=summary, driver=driver,
    )
    metrics = {}
    for m in (p["per_layer"] if args.trace else p["end_to_end"]):
        reader = _load(_find("metrics", m["name"], ".py", dirs),
                       f"bench.metrics.{m['name']}")
        v = reader.read(readings)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": mem_peak}
    result = {"correct": correct, "attempted": driver.rounds, "failed": 0,
              "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.mean_busy_s()
        device["window_s"] = summary.window_s
        result["breakdown"] = trace.breakdown(summary)
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    try:
        result = run_cell(args)
    except Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
