"""Record a small trace of one unit of work on the chip, in the reduced form
the tests read (benchmark/data), and print what the profiler's file holds:

    python3 -m benchmark.tools.record_trace --workload higgs_fit \
        --rows 200000 --iterations 2 --out chiprun_out/trace_small.json.gz
    python3 -m benchmark.tools.record_trace --workload bert_base_fit \
        --rehearsal --out chiprun_out/trace_trainer_small.json.gz

``--rehearsal`` takes the sizes the cell's files give under ``rehearsal``.
"""

from __future__ import annotations

import argparse
import collections
import os
import shutil
import sys

from benchmark import run as harness
from benchmark import trace as tr


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rows", type=int)
    ap.add_argument("--iterations", type=int)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--out", required=True)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    _, cell, config, traffic = harness.load_cell(args.workload,
                                                 args.rehearsal)
    if args.rows:
        config["table"]["rows_per_chip"] = args.rows
    if args.iterations:
        config["numIterations"] = args.iterations
    import jax

    entry = harness._load_module("entries", traffic["entry"]).Entry(
        config, traffic, args.seed, int(cell["chips"]))
    entry.setup()
    tdir = os.path.join(harness.ROOT, ".bench_cache", "trace_record")
    shutil.rmtree(tdir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(tdir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(harness.WINDOW_SPAN):
        entry.unit()
    jax.profiler.stop_trace()
    path = tr.newest_xplane(tdir)
    print("xplane bytes", os.path.getsize(path))
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            ev = list(line.events)
            print("  LINE", line.name, len(ev),
                  [(e.name, e.start_ns, e.duration_ns) for e in ev[:2]])
    trace = tr.load(path)
    for name, start, dur in tr.host_annotations(trace):
        if name == harness.WINDOW_SPAN:
            window = (start, start + dur)
    trace = tr.clip(trace, *window)
    # host lines: keep the annotations only, the runtime's own events are many
    keep = ("bench.", "trainingIterations", "dataPreparation",
            "referenceDataset", "LightGBM", "trainer.", "DeepText")
    for plane in trace["planes"]:
        if plane["name"].startswith("/host:CPU"):
            for line in plane["lines"]:
                line["events"] = [e for e in line["events"]
                                  if e[0].startswith(keep)]
            plane["lines"] = [l for l in plane["lines"] if l["events"]]
    for name, events in tr.device_ops(trace).items():
        own = tr.own_time_by_name(events)
        print("DEVICE", name, len(events), "events; busy",
              tr.busy_ns(events) / 1e9, "window", (window[1] - window[0]) / 1e9)
        for k, v in sorted(own.items(), key=lambda kv: -kv[1])[:40]:
            print(f"   {v:10.6f} s  {k}")
        names = collections.Counter(e[0] for e in events)
        print("   names with 'hist' or 'custom':",
              {k: v for k, v in names.items()
               if "hist" in k.lower() or "custom" in k.lower()})
    print("host annotations:", collections.Counter(
        e[0] for e in tr.host_annotations(trace)).most_common(20))
    print("spans", getattr(entry, "spans", None), "fit_s",
          getattr(entry, "fit_seconds", None))
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    trace["device_kind"] = jax.devices()[0].device_kind
    tr.save(trace, args.out)
    print("saved", args.out, os.path.getsize(args.out), "bytes")
    shutil.rmtree(tdir, ignore_errors=True)
    entry.check_inputs()         # an entry that runs a thread ends it here
    entry.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
