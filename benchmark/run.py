"""The benchmark's one command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s>
        --trace <0|1>

One process drives the chip(s) of one cell of ``BENCHMARK.json``: set-up
(weights from the seed, compiles or cache hits, the correctness check,
warm-up of the cell's own shapes), then one measured window.  Every line
printed is one JSON object; the LAST line is the result the driver reads:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device`` and, traced, ``breakdown``.

It refuses to start (non-zero, no result line) unless jax's first device
is a TPU that ``peaks.json`` knows and the device count is the cell's
``chips``.  There is no option that lets it run elsewhere; the CPU
rehearsals live in ``benchmark/tests``.

This file knows no cell, model, traffic or metric by name.  It finds,
by the names in ``BENCHMARK.json``:

    <paths[0]>/workloads/<cell>.json        kind, traffic parameters
    <config's file>                         sizes, builder
    <paths[0]>/kinds/<kind>.py              run(bench) -> result
    <paths[0]>/models/<builder>.py          the program's normal entry points
    <paths[0]>/traffic/<generator>.py       the general generators
    <paths[0]>/layer_metrics/<metric>.json  reader "<module>:<function>"
    <paths[0]>/readers/<module>.py          sources -> value or None

so a later PR adds files and entries, and edits nothing that is here.
"""
import time

_T0 = time.perf_counter()           # process start, as near as Python gets

import argparse                     # noqa: E402
import contextlib                   # noqa: E402
import importlib.util               # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import shutil                       # noqa: E402
import sys                          # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MANIFEST = "BENCHMARK.json"
RUNS_DIR = ".bench_runs"            # run files and traces; .gitignore has it


class BenchmarkError(Exception):
    """The manifest or one of the cell's files is wrong."""


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_piece(root, bench_dir, group, name):
    """The module ``<bench_dir>/<group>/<name>.py`` of the checkout at
    ``root``, loaded from its file (so a cell added as files is found
    wherever the checkout lies)."""
    path = os.path.join(root, bench_dir, group, name + ".py")
    if not os.path.isfile(path):
        raise BenchmarkError(f"no {group} module {name!r}: {path} is missing")
    mod_name = f"_bench_{group}_{name}".replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _for_cell(entries, cell):
    return [m for m in entries
            if "workloads" not in m or cell in m["workloads"]]


def resolve_cell(root, name):
    """Everything one cell needs, found by name from the manifest at
    ``root``: a dict with the manifest's entries (``workload``,
    ``config_entry``), the files' contents (``spec``, ``config``), the
    cell's metrics (``end_to_end``; ``per_layer`` as (entry, file, reader
    function) triples) and the modules (``kind``, ``model``, ``traffic``).
    Raises ``BenchmarkError`` naming what is missing."""
    manifest = load_json(os.path.join(root, MANIFEST))
    bench_dir = manifest["paths"][0]
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise BenchmarkError(
            f"no workload {name!r} in {MANIFEST} (has: {sorted(cells)})")
    workload = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    if workload["config"] not in configs:
        raise BenchmarkError(
            f"{name}: configuration {workload['config']!r} is not in "
            f"{MANIFEST}")
    config_entry = configs[workload["config"]]
    config = load_json(os.path.join(root, config_entry["file"]))
    spec_path = os.path.join(root, bench_dir, "workloads", name + ".json")
    if not os.path.isfile(spec_path):
        raise BenchmarkError(f"{name}: {spec_path} is missing")
    spec = load_json(spec_path)
    for key, want in (("config", workload["config"]),
                      ("chips", workload["chips"])):
        if spec.get(key) != want:
            raise BenchmarkError(
                f"{name}: {spec_path} says {key}={spec.get(key)!r}, "
                f"{MANIFEST} says {want!r}")

    def piece(group, mod):
        return load_piece(root, bench_dir, group, mod)

    per_layer = []
    for entry in _for_cell(manifest["per_layer"], name):
        mfile = load_json(os.path.join(
            root, bench_dir, "layer_metrics", entry["name"] + ".json"))
        mod, _, fn = mfile["reader"].partition(":")
        per_layer.append((entry, mfile, getattr(piece("readers", mod), fn)))
    return {
        "name": name, "root": root, "bench_dir": bench_dir,
        "manifest": manifest, "workload": workload, "chips": workload["chips"],
        "config_entry": config_entry, "config": config, "spec": spec,
        "end_to_end": _for_cell(manifest["end_to_end"], name),
        "per_layer": per_layer,
        "kind": piece("kinds", spec["kind"]),
        "model": piece("models", config["builder"]),
        "traffic": piece("traffic", spec["traffic"]["generator"]),
    }


def emit(**kw):
    print(json.dumps(kw), flush=True)


class Bench:
    """What a kind's ``run`` gets: the cell, the run's arguments, the
    devices, and the window's bookkeeping (set-up clock, profiler,
    compile counter, the benchmark's own spans)."""

    def __init__(self, cell, seed, seconds, trace, devices, peaks, t0):
        self.cell, self.seed, self.seconds = cell, int(seed), float(seconds)
        self.trace, self.devices, self.peaks, self.t0 = (
            bool(trace), devices, peaks, t0)
        self.spec, self.config = cell["spec"], cell["config"]
        self.model, self.traffic = cell["model"], cell["traffic"]
        self.emit = emit
        self.setup_s = self.window_s = None
        self.compiles_in_window = 0
        self.compile_events = []
        self.cache = {"hits": 0, "misses": 0}
        self._in_window = False
        self.trace_dir = os.path.join(
            cell["root"], RUNS_DIR, cell["name"], "trace")

    def seeds(self, n):
        """``n`` 31-bit seeds drawn from ``--seed`` (which may be wider
        than 32 signed bits)."""
        import numpy as np

        state = np.random.SeedSequence(self.seed).generate_state(n)
        return [int(s) & 0x7FFFFFFF for s in state]

    def window_seconds(self):
        """A traced run measures a short window: traces are large."""
        if self.trace:
            return min(self.seconds,
                       float(self.spec.get("trace_seconds", 5)))
        return self.seconds

    def span(self, name, **kw):
        import jax

        return jax.profiler.TraceAnnotation("bench/" + name, **kw)

    def listen(self):
        import jax

        def on_event(event, **_):
            key = event.rsplit("/", 1)[-1]
            if event.startswith("/jax/compilation_cache/") and \
                    key in ("cache_hits", "cache_misses"):
                self.cache[key[6:]] += 1

        def on_duration(event, duration, **_):
            if self._in_window and "compile" in event:
                self.compiles_in_window += 1
                self.compile_events.append(event)

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)

    @contextlib.contextmanager
    def window(self):
        """The measured window.  Set-up ends where it opens; traced, the
        profiler runs exactly over it."""
        import jax

        self.setup_s = time.perf_counter() - self.t0
        emit(phase="window_open", setup_s=self.setup_s,
             cold=self.cache["misses"] > 0, cache=dict(self.cache))
        if self.trace:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._in_window = True
        t = time.perf_counter()
        try:
            with self.span("window"):
                yield
        finally:
            self.window_s = time.perf_counter() - t
            self._in_window = False
            if self.trace:
                jax.profiler.stop_trace()


def require_devices(chips, peaks):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"benchmark: needs a TPU; jax found {devs[0].platform!r} "
            f"({len(devs)} device(s)) - nothing was run")
    if len(devs) != chips:
        raise SystemExit(
            f"benchmark: the cell asks for {chips} chip(s), jax sees "
            f"{len(devs)} - nothing was run")
    if devs[0].device_kind not in peaks:
        raise SystemExit(
            f"benchmark: no peaks for device kind "
            f"{devs[0].device_kind!r} in peaks.json - nothing was run")
    return devs


def memory_peak(stats):
    """Peak bytes of one chip: the high-water mark of live buffers plus
    that of what loaded programs reserve for their temporaries.  On this
    runtime ``peak_bytes_in_use`` counts only the first (1.9 GB for a
    BERT-base step whose ``memory_analysis`` needs 14.7 GB, of which
    ``peak_bytes_reserved`` reads 12.7 GB); the two marks may fall at
    different moments, so the sum is an upper bound of the true peak."""
    if not stats or "peak_bytes_in_use" not in stats:
        return None
    return stats["peak_bytes_in_use"] + stats.get("peak_bytes_reserved", 0)


def device_line(devs):
    peaks = [memory_peak(d.memory_stats()) for d in devs]
    peaks = [p for p in peaks if p is not None]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": max(peaks) if peaks else None}


def layer_metrics(cell, sources):
    """{name: {value, unit}} of the cell's per-layer metrics; a reader
    that finds nothing to read returns None and its metric is left out."""
    out = {}
    for entry, mfile, reader in cell["per_layer"]:
        value = reader(sources, mfile.get("params", {}))
        if value is not None:
            out[entry["name"]] = {"value": float(value),
                                  "unit": entry["unit"]}
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)        # paddle_tpu and benchmark.* by name
    cell = resolve_cell(ROOT, args.workload)
    peaks = load_json(os.path.join(ROOT, cell["bench_dir"], "peaks.json"))

    import jax

    import paddle_tpu  # noqa: F401 - a checkout without the program fails here

    # jax's persistent compile cache: where the environment says, else
    # the executor's own fixed default inside the checkout.  Small
    # programs are cached too, so that a second run compiles nothing.
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_compile_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    devs = require_devices(cell["chips"], peaks)
    bench = Bench(cell, args.seed, args.seconds, args.trace, devs,
                  peaks[devs[0].device_kind], _T0)
    bench.listen()
    emit(phase="start", since_process_start_s=time.perf_counter() - _T0,
         workload=cell["name"], seed=bench.seed,
         seconds=bench.seconds, trace=bench.trace, jax=jax.__version__,
         device={"platform": devs[0].platform, "kind": devs[0].device_kind,
                 "count": len(devs)},
         cache_dir=jax.config.jax_compilation_cache_dir)

    result = cell["kind"].run(bench)

    correct = bool(result["correct"]) and bench.compiles_in_window == 0
    device = device_line(devs)
    line = {"correct": correct, "attempted": int(result["attempted"]),
            "failed": int(result["failed"])}
    emit(phase="window_closed", window_s=bench.window_s,
         compiles_in_window=bench.compiles_in_window,
         compile_events=bench.compile_events[:8],
         cache=dict(bench.cache), memory_stats=devs[0].memory_stats(),
         checks=result.get("checks"),
         info=result.get("info"))
    if bench.trace:
        from benchmark import trace_reduce

        kernels = {}
        for _, mfile, _ in cell["per_layer"]:
            kernels.update(mfile.get("kernels", {}))
        red = trace_reduce.reduce(
            trace_reduce.find_xplane(bench.trace_dir), kernels=kernels)
        sources = dict(result["sources"], trace=red, peaks=bench.peaks,
                       config=cell["config"], spec=cell["spec"],
                       window_s=bench.window_s)
        line["metrics"] = layer_metrics(cell, sources)
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        line["breakdown"] = {"device_ops": red["device_ops"],
                             "idle_gaps": red["idle_gaps"]}
    else:
        values = dict(result["end_to_end"], setup_s=bench.setup_s)
        line["metrics"] = {
            m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in cell["end_to_end"]}
    line["device"] = device
    out_dir = os.path.join(ROOT, RUNS_DIR, cell["name"])
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"last_trace{int(bench.trace)}.json"),
              "w") as f:
        json.dump({"args": vars(args), "result": line,
                   "checks": result.get("checks"),
                   "info": result.get("info")}, f, indent=1)
    print(json.dumps(line), flush=True)
    return 0 if correct else 1


if __name__ == "__main__":
    sys.exit(main())
