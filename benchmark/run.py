"""One run of one cell:

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process: it loads, warms up, measures for ``--seconds``, checks what the
timed path produced against the configuration's plain reference, prints one
JSON object as the last line of standard output and exits. Without a TPU (or
with fewer chips than the cell asks for) it exits non-zero and prints no
number. ``--rehearsal`` runs the same code on the CPU at the tiny sizes the
files give under ``rehearsal`` and reduces the small recorded trace kept in
``benchmark/data``; its line says so and it exits 4: it is never a
measurement.

Nothing here names a cell, a configuration, a traffic mix or a metric. They
are found by name: ``BENCHMARK.json`` -> ``configs/<config>.json``,
``traffic/<mix>.json`` -> ``entries/<entry>.py``, ``references/<config>.py``,
``metrics/<metric>.py``.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()          # set-up is counted from here

import argparse                    # noqa: E402
import importlib.util              # noqa: E402
import json                        # noqa: E402
import os                          # noqa: E402
import shutil                      # noqa: E402
import sys                         # noqa: E402
import threading                   # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
REHEARSAL_EXIT = 4
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
WINDOW_SPAN = "bench.window"


class CompileEvents:
    """Sum and count of jax's backend-compile events (a retrieval from the
    persistent cache is one too), as ``chip_smoke.Phases`` counts them."""

    def __init__(self):
        import jax.monitoring as monitoring

        self._lock = threading.Lock()
        self.seconds, self.count = 0.0, 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            with self._lock:
                self.seconds += duration
                self.count += 1

    def read(self):
        with self._lock:
            return self.seconds, self.count


def _load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _load_module(kind: str, name: str):
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    sys.exit(f"benchmark: no workload {name!r} in BENCHMARK.json")


def load_cell(name: str, rehearsal: bool = False) -> tuple:
    """(BENCHMARK.json, the cell's entry in it, its configuration, its
    traffic mix), the last two with their ``rehearsal`` sizes if asked."""
    bench = _load_json(ROOT, "BENCHMARK.json")
    cell = _cell(bench, name)
    config = _load_json(HERE, "configs", cell["config"] + ".json")
    traffic = _load_json(HERE, "traffic", cell["traffic"] + ".json")
    if rehearsal:
        config, traffic = _merge_rehearsal(config), _merge_rehearsal(traffic)
    return bench, cell, config, traffic


def _applies(metric: dict, cell: str, reported: set) -> bool:
    """A per-layer metric is read in the cells it lists, or, listing none, in
    every cell that reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric["moves"] in reported


def _device(chips: int, rehearsal: bool):
    import jax

    devs = jax.devices()
    if not rehearsal:
        if devs[0].platform != "tpu":
            sys.exit(f"benchmark: no TPU (jax.devices()[0] is "
                     f"{devs[0].platform!r}); nothing measured")
        if len(devs) < chips:
            sys.exit(f"benchmark: the cell needs {chips} chip(s), jax sees "
                     f"{len(devs)}; nothing measured")
    return devs


def _memory_peak(devs) -> int:
    """The fullest device's peak: its arrays (``peak_bytes_in_use``) and
    what the runtime set aside for the compiled programs' temporaries
    (``peak_bytes_reserved``), which the TPU's allocator keeps in a
    reservation of its own and leaves out of the first number."""
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


def use_compile_cache() -> None:
    """The compile cache: where the environment says, else one fixed
    directory inside the checkout; everything is cached, however small. The
    variable is set too: the program sets no directory of its own where it
    finds it (core/compile_cache.py)."""
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".bench_cache", "jax")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    import jax

    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _merge_rehearsal(d: dict) -> dict:
    out = dict(d)
    for k, v in d.get("rehearsal", {}).items():
        out[k] = {**out[k], **v} if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--readings", help="also read the control and the "
                    "planted faults on this run's answer and write them to "
                    "this file (for setting limits; no benchmark run does)")
    args = ap.parse_args(argv)

    bench, cell, config, traffic = load_cell(args.workload, args.rehearsal)
    if args.rehearsal:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell['chips']}")

    use_compile_cache()
    import jax

    devs = _device(int(cell["chips"]), args.rehearsal)
    compiles = CompileEvents()

    entry_mod = _load_module("entries", traffic["entry"])
    entry = entry_mod.Entry(config, traffic, args.seed, int(cell["chips"]))
    with jax.profiler.TraceAnnotation("bench.setup"):
        entry.setup()
    compile_s, _ = compiles.read()
    setup_s = time.perf_counter() - _T0

    # ---- the window -------------------------------------------------------
    trace_dir = os.path.join(ROOT, ".bench_cache", "trace")
    tracing = bool(args.trace) and not args.rehearsal
    if tracing:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    _, compiles_before = compiles.read()
    work, units = 0, 0
    t0 = time.perf_counter()
    while True:
        span = WINDOW_SPAN if units == 0 else "bench.unit"
        with jax.profiler.TraceAnnotation(span):
            work += entry.unit()
        units += 1
        if tracing and units == 1:
            # one unit is traced: a trace of the whole window would be
            # larger than what may be written, and reading it slower
            jax.profiler.stop_trace()
        if time.perf_counter() - t0 >= args.seconds:
            break
    window_s = time.perf_counter() - t0
    _, compiles_after = compiles.read()
    window_compiles = compiles_after - compiles_before
    print(f"window: {units} unit(s), {work} of work, {window_s:.4f} s; "
          f"compile events in the window: {window_compiles}", flush=True)
    memory_peak = _memory_peak(devs)

    # ---- correct: the timed path's last answer against the reference ------
    inputs = entry.check_inputs()
    per_layer_ctx = {
        "entry": entry, "config": config, "chips": int(cell["chips"]),
        "compile_s": compile_s, "device_kind": devs[0].device_kind,
        "trees": (entry.trees() if args.trace and hasattr(entry, "trees")
                  else None),
    }
    entry.release()
    ref_mod = _load_module("references", cell["config"])
    numbers = ref_mod.check(config, inputs)
    if args.readings:
        from benchmark.tools import readings

        readings.write(args.readings, ref_mod, config, traffic, inputs,
                       args.seed, numbers)
    numbers["window_compiles"] = float(window_compiles)
    limits = dict(config["limits"], window_compiles=0.0)
    compared = {k: {"value": float(v), "limit": float(limits[k])}
                for k, v in numbers.items()}
    correct = all(c["value"] <= c["limit"] for c in compared.values())

    # ---- metrics ------------------------------------------------------------
    end_to_end = {"setup_s": setup_s,
                  traffic["rate_metric"]: work / window_s / int(cell["chips"])}
    units_of = {m["name"]: m["unit"]
                for m in bench["end_to_end"] + bench["per_layer"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    result = {"correct": bool(correct), "attempted": units, "failed": 0}
    if args.trace:
        from benchmark import trace as tr

        if args.rehearsal:
            trace = tr.load(os.path.join(HERE, "data",
                                         config["rehearsal_trace"]))
            # the recorded trace is a chip's: its peaks, not the CPU's
            per_layer_ctx["device_kind"] = trace["device_kind"]
        else:
            trace = tr.load(tr.newest_xplane(trace_dir))
            shutil.rmtree(trace_dir, ignore_errors=True)
        window = None
        for name, start, dur in tr.host_annotations(trace):
            if name == WINDOW_SPAN:
                window = (start, start + dur)
        if window is None:
            sys.exit("benchmark: the trace holds no bench.window span")
        trace = tr.clip(trace, *window)
        busy_s, traced_s = tr.busy_and_window(trace, window)
        if busy_s <= 0:
            sys.exit("benchmark: no operation ran on the device in the trace")
        device["busy_s"], device["window_s"] = busy_s, traced_s
        per_layer_ctx.update(trace=trace, traced_s=traced_s)
        metrics = {}
        for m in bench["per_layer"]:
            if not _applies(m, cell["name"], set(end_to_end)):
                continue
            reader = _load_module("metrics", m["name"])
            value = reader.read(per_layer_ctx) if reader else None
            if value is not None:
                metrics[m["name"]] = float(value)
        result["breakdown"] = tr.breakdown(trace, window)
    else:
        metrics = end_to_end
    result["metrics"] = {k: {"value": v, "unit": units_of[k]}
                         for k, v in metrics.items()}
    result["device"] = device
    if args.rehearsal:
        result["rehearsal"] = True
    result["compared"] = compared
    for k, c in compared.items():
        print(f"compared {k}: {c['value']:.6g} (limit {c['limit']:.6g})",
              file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return REHEARSAL_EXIT if args.rehearsal else 0


if __name__ == "__main__":
    sys.exit(main())
