"""Minimal XSpace (.xplane.pb) reader: op-level time breakdown without
TensorBoard.

``jax.profiler.trace`` writes TensorFlow-profiler XSpace protobufs; the
usual consumer (tensorboard-plugin-profile) is not in this image, so this
parses the wire format directly — the same self-contained approach as the
repo's ONNX reader (synapseml_tpu/onnx/protoio.py) — and aggregates XLA op
durations by name/category. This is the tool that localized the GBDT
hot-loop cost on-chip (docs/trace_summary_gbdt.md).

Usage:
  python tools/trace_summary.py /tmp/jaxtrace [--top 30] [--by op|category]

Schema subset (tsl/profiler/protobuf/xplane.proto):
  XSpace.planes=1; XPlane{id=1,name=2,lines=3,event_metadata=4(map),
  stat_metadata=5(map)}; XLine{name=3,events=6}; XEvent{metadata_id=1,
  duration_ps=3}; XEventMetadata{id=1,name=2,display_name=4}.
"""
from __future__ import annotations

import glob
import os
import sys
from collections import defaultdict


def _varint(buf: bytes, i: int):
    r = 0
    s = 0
    while True:
        b = buf[i]
        i += 1
        r |= (b & 0x7F) << s
        if not b & 0x80:
            return r, i
        s += 7


def _fields(buf: bytes):
    """Yield (field_number, wire_type, value) over one message's bytes.
    value: int for varint/fixed, memoryview for length-delimited."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        fno, wt = tag >> 3, tag & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 1:
            v = int.from_bytes(buf[i:i + 8], "little")
            i += 8
        elif wt == 2:
            ln, i = _varint(buf, i)
            v = buf[i:i + ln]
            i += ln
        elif wt == 5:
            v = int.from_bytes(buf[i:i + 4], "little")
            i += 4
        else:
            raise ValueError(f"unsupported wire type {wt}")
        yield fno, wt, v


def _parse_event_metadata(buf: bytes):
    """map<int64, XEventMetadata> entry → (id, name or display_name)."""
    key, name, disp = 0, "", ""
    for fno, _, v in _fields(buf):
        if fno == 1:
            key = v
        elif fno == 2:
            for f2, _, v2 in _fields(v):          # XEventMetadata
                if f2 == 1:
                    key = key or v2
                elif f2 == 2:
                    name = bytes(v2).decode("utf-8", "replace")
                elif f2 == 4:
                    disp = bytes(v2).decode("utf-8", "replace")
    return key, (disp or name)


def parse_xplane(path: str):
    """Returns [(plane_name, line_name, [(event_name, duration_ps), ...])]."""
    with open(path, "rb") as f:
        space = f.read()
    out = []
    for fno, _, plane in _fields(space):
        if fno != 1:
            continue
        pname = ""
        metas = {}
        lines = []
        for f1, _, v in _fields(plane):
            if f1 == 2:
                pname = bytes(v).decode("utf-8", "replace")
            elif f1 == 4:
                k, nm = _parse_event_metadata(v)
                metas[k] = nm
            elif f1 == 3:
                lines.append(v)
        for line in lines:
            lname = ""
            events = []
            for f2, _, v in _fields(line):
                if f2 == 2:                       # XLine.name
                    lname = bytes(v).decode("utf-8", "replace")
                elif f2 == 4:                     # XLine.events
                    mid, dur = 0, 0
                    for f3, _, v3 in _fields(v):
                        if f3 == 1:               # XEvent.metadata_id
                            mid = v3
                        elif f3 == 3:             # XEvent.duration_ps
                            dur = v3
                    events.append((mid, dur))
            out.append((pname, lname,
                        [(metas.get(m, f"#{m}"), d) for m, d in events]))
    return out


_CATEGORIES = (
    ("sort", "sort"),
    ("scatter", "scatter"),
    ("gather", "gather"),
    ("dynamic-slice", "slice"),
    ("dynamic_slice", "slice"),
    ("dynamic-update-slice", "slice"),
    ("custom-call", "custom-call(pallas)"),
    ("fusion", "fusion"),
    ("convolution", "conv"),
    ("dot", "dot"),
    ("copy", "copy"),
    ("all-reduce", "collective"),
    ("transpose", "transpose"),
    ("reduce", "reduce"),
    ("iota", "elementwise"),
    ("select", "elementwise"),
    ("broadcast", "elementwise"),
)


def categorize(name: str) -> str:
    low = name.lower()
    for key, cat in _CATEGORIES:
        if key in low:
            return cat
    return "other"


def summarize(trace_dir: str, top: int = 30, by: str = "op"):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        print(f"no .xplane.pb under {trace_dir}")
        return 1
    path = paths[-1]                       # newest session
    agg = defaultdict(lambda: [0, 0])      # name -> [total_ps, count]
    device_total = 0
    parsed = parse_xplane(path)
    # device op planes: '/device:TPU:0' etc. with 'XLA Ops' lines. Fallback
    # for the CPU backend (parser validation): XLA executor thread lines.
    selected = [(p, l, e) for p, l, e in parsed
                if "/device" in p.lower() and "op" in l.lower()]
    if not selected:
        selected = [(p, l, e) for p, l, e in parsed if "XLA" in l]
    for pname, lname, events in selected:
        for name, dur in events:
            key = categorize(name) if by == "category" else name
            agg[key][0] += dur
            agg[key][1] += 1
            device_total += dur
    if not agg:
        print(f"no device op events in {path} (planes: "
              f"{[p for p, _, _ in parse_xplane(path)][:8]})")
        return 1
    print(f"# {path}")
    print(f"# device op time total: {device_total/1e9:.3f} ms "
          f"(sum over ops; overlapping lines may double-count)")
    rows = sorted(agg.items(), key=lambda kv: -kv[1][0])[:top]
    w = max(len(k) for k, _ in rows)
    for name, (ps, cnt) in rows:
        print(f"{name:<{w}}  {ps/1e9:10.3f} ms  {cnt:7d}x  "
              f"{100*ps/max(device_total,1):5.1f}%")
    return 0


if __name__ == "__main__":
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    top = int(sys.argv[sys.argv.index("--top") + 1]) \
        if "--top" in sys.argv else 30
    by = sys.argv[sys.argv.index("--by") + 1] if "--by" in sys.argv else "op"
    sys.exit(summarize(args[0] if args else "/tmp/jaxtrace", top, by))
