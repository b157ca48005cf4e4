"""Run one benchmark cell once and print its result as the last line.

    python benchmarks/chip/run_cell.py --workload <name> --seed <n>
        --seconds <s> --trace <0|1>

Everything runs in this one process.  The cell is looked up by name in
``BENCHMARK.json``; its deployment is ``configs/<config>.json``, its
traffic ``traffic/<traffic>.json``, which names the driver
(``drivers/<driver>.py``) that sets the program up, warms the cell's own
shapes, runs the measured window and checks what the window produced
against the plain reference, within the limits of ``checks/<driver>.json``.  ``--trace 1`` runs the window under the
JAX profiler and reports the cell's per-layer metrics, each read by
``metrics/<metric>.py`` from the reduced trace; ``--trace 0`` reports the
end-to-end metrics, timed on the host clock.

Without a TPU, or with fewer chips than the cell asks for, it exits with
status 2 and prints no result.  JAX's persistent compilation cache lives
at ``<checkout>/.jax_cache``, so only a cell's first run in a checkout
compiles.
"""
import time

T_START = time.perf_counter()

import argparse
import importlib.util
import json
import os
import shutil
import sys
import tempfile
from contextlib import contextmanager, nullcontext
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CACHE_DIR = ROOT / ".jax_cache"


class NoChip(Exception):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str) -> dict:
    """The cell ``name`` with its deployment, traffic, and the metrics it
    reports (end-to-end and per-layer entries of ``BENCHMARK.json``)."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload named {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    return {"cell": cell,
            "cfg": json.loads((ROOT / conf["file"]).read_text()),
            "traffic": json.loads(
                (HERE / "traffic" / f"{cell['traffic']}.json").read_text()),
            "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
            "per_layer": [m for m in bench["per_layer"] if applies(m)]}


def device_info(jax, chips: int, require_tpu: bool):
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devs)}")
    used = devs[:chips]
    return used, {"platform": devs[0].platform,
                  "kind": devs[0].device_kind, "count": len(used)}


class CompileCounter:
    """JAX traces and backend compiles, from ``jax.monitoring``."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self, jax):
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.n += 1


@contextmanager
def profiled(jax, out_dir: str):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0          # spans only, no Python calls
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def per_layer(metrics, summary, ctx) -> dict:
    """Each per-layer metric, read by its own reader; a reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    for m in metrics:
        reader = load_module(HERE / "metrics" / f"{m['name']}.py")
        got = reader.read(summary, ctx)
        if got is None:
            continue
        entry = got if isinstance(got, dict) else {"value": float(got)}
        entry = {"value": float(entry.pop("value")), "unit": m["unit"],
                 **entry}
        out[m["name"]] = entry
    return out


def run(argv=None, require_tpu: bool = True, trace_out=None,
        traffic_overrides=None, compile_cache: bool = True):
    """One run of one cell; returns the result line's object and the
    check's lines.  Raises ``NoChip`` without the chips the cell needs.
    The keyword arguments serve rehearsals and tests on the CPU (skip the
    look for a chip, shrink the traffic, leave JAX's compilation cache as
    it is) and the recording of the trace that the trace reduction's test
    reads (``trace_out``: where to copy the ``.xplane.pb``)."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_cell(args.workload)
    cfg, traffic = spec["cfg"], {**spec["traffic"],
                                 **(traffic_overrides or {})}
    chips = int(spec["cell"]["chips"])

    import jax
    if compile_cache:
        CACHE_DIR.mkdir(exist_ok=True)
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    used, device = device_info(jax, chips, require_tpu)

    sys.path[:0] = [str(ROOT / "src"), str(HERE)]
    driver_mod = load_module(HERE / "drivers" / f"{traffic['driver']}.py")
    span = jax.profiler.TraceAnnotation if args.trace else \
        (lambda name: nullcontext())
    drv = driver_mod.Driver(cfg, traffic, args.seed, span)
    compiles = CompileCounter(jax)
    drv.setup()
    setup_s = time.perf_counter() - T_START

    n0, t0 = compiles.n, drv.trace_count()
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace \
        else None
    try:
        if args.trace:
            with profiled(jax, trace_dir):
                with jax.profiler.TraceAnnotation("bench.window"):
                    win = drv.window(min(args.seconds,
                                         float(traffic["trace_seconds"])))
        else:
            win = drv.window(args.seconds)
    except BaseException:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
        raise
    window_compiles = compiles.n - n0 + drv.trace_count() - t0
    attempted, failed = drv.finish()
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in used)
    device["memory_peak_bytes"] = peak

    result = {"correct": None, "attempted": int(attempted),
              "failed": int(failed)}
    if args.trace:
        import trace_reduce
        files = list(Path(trace_dir).rglob("*.xplane.pb"))
        summary = trace_reduce.reduce_file(files[0])
        lo, hi = trace_reduce.window_of(summary)
        busy = trace_reduce.device_busy(summary)
        device["busy_s"] = sum(busy.values()) / max(len(busy), 1)
        device["window_s"] = (hi - lo) / 1e9
        peaks = json.loads((HERE / "peaks.json").read_text())
        if device["kind"] not in peaks and require_tpu:
            raise SystemExit(f"no peaks for device kind {device['kind']!r}")
        ctx = {"span": drv.span, "compiles": window_compiles,
               "cfg": cfg, "traffic": traffic,
               "peak": peaks.get(device["kind"]), "busy": busy}
        result["metrics"] = per_layer(spec["per_layer"], summary, ctx)
        result["breakdown"] = trace_reduce.breakdown(summary)
        for dev, b in sorted(busy.items()):
            print(f"idle share {dev}: {1.0 - b / device['window_s']!r}",
                  flush=True)
        if trace_out:
            shutil.copy(files[0], trace_out)
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        result["metrics"] = {
            m["name"]: {"value": float(win["metrics"][m["name"]]),
                        "unit": m["unit"]}
            for m in spec["end_to_end"] if m["name"] != "setup_s"}
        result["metrics"]["setup_s"] = {"value": setup_s, "unit": "s"}
    print(f"window: {win['calls']} calls, window_compiles "
          f"{window_compiles}", flush=True)
    result["device"] = device

    # the reference runs only once the program's state is freed
    drv.release()
    jax.clear_caches()
    limits = json.loads((HERE / "checks" /
                         f"{traffic['driver']}.json").read_text())["limits"]
    t_check = time.perf_counter()
    numbers, what = jax.block_until_ready(drv.check())
    import compare
    result["correct"] = compare.judge(numbers, limits)
    lines = compare.check_lines(numbers, limits)
    print(f"check: {what} compared in "
          f"{time.perf_counter() - t_check:.1f} s", flush=True)
    result["check"] = {k: {"value": float(numbers[k]), "limit": limits[k]}
                       for k in limits}
    return result, lines


def main(argv=None) -> int:
    try:
        result, lines = run(argv)
    except NoChip as e:
        print(f"run_cell: {e}", file=sys.stderr)
        return 2
    for line in lines:
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
