"""One run of one cell: `python3 -m benchmark.run --workload <name> --seed <n>
--seconds <s> --trace <0|1>`.

Everything that belongs to one configuration, one traffic mix or one per-layer
metric is a file of its own, found by the name in BENCHMARK.json:

    <config>.file                              the configuration
    benchmark/workloads/<traffic>.json         the traffic mix (names a driver)
    benchmark/drivers/<driver>.py              one of the general drivers
    benchmark/layer_metrics/<metric>.py        one reader per per-layer metric

The run: refuse to start without the chips the cell asks for, point JAX's
persistent compile cache at a fixed directory of the checkout, set up (build,
weights from the seed, warm-up of this cell's shapes) and count that as
`setup_s`, measure for `--seconds`, read the device's memory, free the
program's state, run the comparison with the plain reference, print one JSON
line. `--trace 1` wraps the window in the profiler and prints the per-layer
metrics instead of the end-to-end ones.

(The reference has no benchmark: nothing of this directory has an analogue
there.)
"""

from __future__ import annotations

import time

T_START = time.monotonic()  # as near to process start as this module gets

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pkgutil  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
MANIFEST = "BENCHMARK.json"


def load_manifest(root: str) -> dict:
    with open(os.path.join(root, MANIFEST)) as f:
        return json.load(f)


def _named(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit("benchmark: no %s named %r in %s (have: %s)"
                     % (what, name, MANIFEST,
                        ", ".join(e["name"] for e in entries)))


def resolve_cell(root: str, manifest: dict, workload: str) -> dict:
    """The cell's files, read: {'cell', 'config' (the file's `fields`),
    'traffic' (the mix's parameters), 'driver' (module)}."""
    cell = _named(manifest["workloads"], workload, "workload")
    conf = _named(manifest["configs"], cell["config"], "config")
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    bench_dir = os.path.join(root, manifest["paths"][0])
    with open(os.path.join(bench_dir, "workloads",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    from . import drivers as package
    drivers = sorted(m.name for m in pkgutil.iter_modules(package.__path__)
                     if not m.name.startswith("_"))
    if traffic["driver"] not in drivers:
        raise SystemExit("benchmark: traffic %r names driver %r; have %s"
                         % (cell["traffic"], traffic["driver"], drivers))
    return {"cell": cell, "config": config, "traffic": traffic,
            "bench_dir": bench_dir,
            "driver": importlib.import_module(
                "benchmark.drivers." + traffic["driver"])}


def metrics_of(manifest: dict, section: str, workload: str):
    """The metrics of `section` that this cell reports."""
    return [m for m in manifest[section]
            if "workloads" not in m or workload in m["workloads"]]


def quantity(e2e: dict, metric: str):
    """A driver names a quantity (`serve_img_per_s`); the manifest may split
    it over cells that need bounds of their own (`serve_img_per_s.hostbound`):
    the part before the first `.` is then the driver's name."""
    return e2e[metric] if metric in e2e else e2e[metric.split(".", 1)[0]]


def load_reader(bench_dir: str, metric: str):
    path = os.path.join(bench_dir, "layer_metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_layer_metric_" + metric.replace(".", "_").replace("-", "_"),
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


class MemoryTracer:
    """What the engine is given as `tracer=` in a traced run: keeps the
    program's own spans (`serve:queue-wait`, `serve:h2d`, ...) in memory as
    (name, start on the monotonic clock or None, seconds). Contexts stay off (`enabled` False), so the engine mints
    no per-request trace ids."""
    enabled = False

    def __init__(self):
        self.records = []

    @contextlib.contextmanager
    def span(self, name, ctx=None, links=None, **meta):
        sp = types.SimpleNamespace(dur_s=None)
        t0 = time.monotonic()
        try:
            yield sp
        finally:
            sp.dur_s = time.monotonic() - t0
            self.records.append((name, t0, sp.dur_s))

    def record(self, name, dur_s, ctx=None, links=None, **meta):
        self.records.append((name, None, float(dur_s)))

    def event(self, name, ctx=None, links=None, **meta):
        pass


class Context:
    """What a driver is handed."""

    def __init__(self, seed, config, traffic, traced, sabotage=None):
        self.sabotage = sabotage  # tests only: see run_cell
        self.seed = int(seed)
        # one dict: what the reference reads and what Config(**fields) takes
        self.config = self.program_fields = config["fields"]
        self.traffic = traffic
        self.traced = bool(traced)
        self.engine_tracer = MemoryTracer() if traced else None
        self.spans = []  # (name, start monotonic, seconds), host clock
        self.marks = []  # host instants of the clock marks (see mark())

    @contextlib.contextmanager
    def span(self, name):
        """A host span of the benchmark's own, on the host's clock."""
        t0 = time.monotonic()
        try:
            yield
        finally:
            self.spans.append((name, t0, time.monotonic() - t0))

    def mark(self):
        """Tie the host's clock to the trace's: run a tiny named program on
        the (idle) device and wait for it; trace_reduce finds its event."""
        import jax
        # an instant, not a duration: graftlint: off=per-call-timing
        t0 = time.monotonic()
        jax.block_until_ready(_bench_mark(0.0))
        self.marks.append((t0 + time.monotonic()) / 2)


def _bench_mark(x):
    import jax
    global _MARK
    if _MARK is None:
        def bench_mark(x):
            return x + 1.0
        _MARK = jax.jit(bench_mark)
    return _MARK(x)


_MARK = None


def acquire_devices(chips: int, allow_cpu: bool):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" and not allow_cpu:
        raise SystemExit("benchmark: no TPU (jax.devices()[0].platform = %r);"
                         " a cell only runs on the chip"
                         % devices[0].platform)
    if len(devices) < chips and not allow_cpu:
        raise SystemExit("benchmark: the cell asks for %d chips, JAX sees %d"
                         % (chips, len(devices)))
    return devices


def use_compile_cache(root: str) -> str:
    """JAX_COMPILATION_CACHE_DIR if the environment sets it (then nothing is
    set here), else <checkout>/build/jax_cache: a fixed path, because the path
    is part of the cache's key. Every program is cached, however quickly it
    compiled: set-up runs dozens of small ones."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, "build", "jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def device_record(devices, chips: int) -> dict:
    """As JAX reports it. The TPU runtime keeps a program's temporaries out of
    `peak_bytes_in_use` and under `peak_bytes_reserved` (PERF.md section 6,
    PR 24), so the peak a chip held is the two together."""
    peak = 0
    for d in devices[:chips]:
        s = d.memory_stats() or {}
        peak = max(peak, int(s.get("peak_bytes_in_use", 0))
                   + int(s.get("peak_bytes_reserved", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": chips, "memory_peak_bytes": peak}


def run_cell(workload: str, seed: int, seconds: float, trace: int,
             root: str = ROOT, allow_cpu: bool = False, sabotage=None
             ) -> dict:
    """Run one cell once; returns the result (the last line's object).
    `allow_cpu` and `sabotage` are for tests alone: the command line exposes
    neither. `sabotage(cell)` is called by the driver's set-up once the timed
    path (runner or engine) is built and before anything runs through it, to
    break that path underneath."""
    manifest = load_manifest(root)
    parts = resolve_cell(root, manifest, workload)
    chips = int(parts["cell"]["chips"])
    devices = acquire_devices(chips, allow_cpu)
    use_compile_cache(root)
    import jax
    ctx = Context(seed, parts["config"], parts["traffic"], trace, sabotage)
    cell = parts["driver"].Cell(ctx)
    cell.setup()
    setup_s = time.monotonic() - T_START
    trace_dir = os.path.join(root, "build", "benchmark_trace", workload)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        # compiled outside the trace (setup_s above is process start to here,
        # nothing per call): graftlint: off=per-call-timing
        jax.block_until_ready(_bench_mark(0.0))
        options = jax.profiler.ProfileOptions()
        # device events only: host tracing at any level slows the traced
        # run to a fraction of the untraced one (trace_reduce.py)
        options.python_tracer_level = 0
        options.host_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        ctx.mark()
    try:
        window = cell.run(float(seconds))
    finally:
        if trace:
            ctx.mark()
            jax.profiler.stop_trace()
    device = device_record(devices, chips)
    cell.free()
    numbers, missing = cell.check()
    from . import compare
    verdict = compare.judge(numbers, parts["traffic"]["limits"], missing)

    e2e = dict(window["e2e"], setup_s=setup_s)
    print("setup_s %.3f window_s %.3f" % (setup_s, window["window_s"]),
          file=sys.stderr)
    result = {"correct": verdict["correct"],
              "attempted": int(window["attempted"]),
              "failed": int(window["failed"])}
    if not trace:
        result["metrics"] = {
            m["name"]: {"value": quantity(e2e, m["name"]), "unit": m["unit"]}
            for m in metrics_of(manifest, "end_to_end", workload)}
    else:
        from . import trace_reduce
        pb = trace_reduce.find_xplane(trace_dir)
        engine_spans = ctx.engine_tracer.records if ctx.engine_tracer else []
        reduced = pb and trace_reduce.reduce_trace(
            pb, ctx.spans + [r for r in engine_spans if r[1] is not None],
            ctx.marks, (window["t0"], window["t0"] + window["window_s"]))
        shutil.rmtree(trace_dir, ignore_errors=True)
        if not reduced:
            if not allow_cpu:
                raise SystemExit("benchmark: the traced run recorded no "
                                 "device operation")
            reduced = {"busy_s": 0.0, "window_s": window["window_s"],
                       "op_ms": {}, "device_ops": [], "idle_gaps": []}
        with open(os.path.join(HERE, "peaks.json")) as f:
            peaks = json.load(f)
        if device["kind"] not in peaks and not allow_cpu:
            raise SystemExit("benchmark: no peaks for device kind %r in "
                             "peaks.json" % device["kind"])
        record = types.SimpleNamespace(
            cell=parts["cell"], config=ctx.config, traffic=ctx.traffic,
            window=window, e2e=e2e, trace=reduced, spans=ctx.spans,
            engine_spans=[(n, d) for n, _, d in engine_spans],
            peaks=peaks.get(device["kind"]))
        result["metrics"] = {}
        for m in metrics_of(manifest, "per_layer", workload):
            value = load_reader(parts["bench_dir"], m["name"])(record)
            if value is not None:
                result["metrics"][m["name"]] = {"value": float(value),
                                                "unit": m["unit"]}
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
    result["device"] = device
    if trace:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checked"] = verdict["checked"]  # last, as the contract asks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python3 -m benchmark.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds, args.trace)
    for name, c in result["checked"].items():
        print("checked %s = %r (limit %r)" % (name, c["value"], c["limit"]),
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
