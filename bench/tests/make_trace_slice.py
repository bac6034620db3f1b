"""Cut one training step out of a traced run's ``.xplane.pb``, for a
recorded slice under ``bench/tests/data/``.

    python bench/tests/make_trace_slice.py <run>/trace/.../host.xplane.pb \
        bench/tests/data/train_step_phases.xplane.pb.gz [--step 2]

The slice runs from the end of the window's ``--step``-th ``jit_step``
module (counted from 1) to the start of the module after the next one, so
it holds one whole step on the device and the host gap on either side.
It keeps the device plane's ``XLA Modules`` and ``XLA Ops`` lines with
their event metadata (an op's name is its HLO text) but not the events'
own stats, which only repeat their timing, and the host events of the
program's spans (``train.*``, ``jit.compile``) that overlap it.
The ``bench.window`` annotation is set over the slice. Written gzipped.
"""
from __future__ import annotations

import argparse
import gzip

DEVICE = "/device:TPU:0"
KEEP_DEVICE_LINES = ("XLA Modules", "XLA Ops")
MARK = "bench.window"
HOST_PREFIXES = ("train.", "jit.compile")


def _ps(line, ev):
    return line.timestamp_ns * 1000 + ev.offset_ps


def cut(space, step: int):
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    dev = next(p for p in space.planes if p.name == DEVICE)
    mods = next(ln for ln in dev.lines if ln.name == "XLA Modules")
    host = [p for p in space.planes if p.name.startswith("/host:")
            and any(len(ln.events) for ln in p.lines)]
    mark = None
    for p in host:
        for ln in p.lines:
            for ev in ln.events:
                if p.event_metadata[ev.metadata_id].name == MARK:
                    mark = (_ps(ln, ev), _ps(ln, ev) + ev.duration_ps)
    runs = sorted((_ps(mods, ev), _ps(mods, ev) + ev.duration_ps)
                  for ev in mods.events
                  if dev.event_metadata[ev.metadata_id].name.startswith(
                      "jit_step") and mark[0] <= _ps(mods, ev) < mark[1])
    lo, hi = runs[step - 1][1], runs[step + 1][0]

    out = xplane_pb2.XSpace()

    def copy_plane(src, keep_line, keep_event):
        p = out.planes.add()
        p.id, p.name = src.id, src.name
        used_ev, used_st = set(), set()
        for ln in src.lines:
            if not keep_line(ln):
                continue
            evs = [ev for ev in ln.events if keep_event(src, ln, ev)]
            if not evs:
                continue
            nl = p.lines.add()
            nl.CopyFrom(ln)
            del nl.events[:]
            nl.timestamp_ns = min(_ps(ln, ev) for ev in evs) // 1000
            for ev in evs:
                ne = nl.events.add()
                ne.CopyFrom(ev)
                ne.offset_ps = _ps(ln, ev) - nl.timestamp_ns * 1000
                used_ev.add(ev.metadata_id)
                used_st.update(s.metadata_id for s in ev.stats)
        for i in used_ev:
            md = src.event_metadata[i]
            p.event_metadata[i].CopyFrom(md)
            used_st.update(s.metadata_id for s in md.stats)
        for i in list(used_st):
            p.stat_metadata[i].CopyFrom(src.stat_metadata[i])
        # stats whose value is a reference name a stat_metadata entry too
        for ln in p.lines:
            for ev in ln.events:
                for s in ev.stats:
                    if s.WhichOneof("value") == "ref_value":
                        p.stat_metadata[s.ref_value].CopyFrom(
                            src.stat_metadata[s.ref_value])
        for md in p.event_metadata.values():
            for s in md.stats:
                if s.WhichOneof("value") == "ref_value":
                    p.stat_metadata[s.ref_value].CopyFrom(
                        src.stat_metadata[s.ref_value])
        return p

    p = copy_plane(dev, lambda ln: ln.name in KEEP_DEVICE_LINES,
                   lambda src, ln, ev: lo <= _ps(ln, ev)
                   and _ps(ln, ev) + ev.duration_ps <= hi)
    for ln in p.lines:              # per-event stats repeat the timing
        for ev in ln.events:
            del ev.stats[:]

    def host_event(src, ln, ev):
        name = src.event_metadata[ev.metadata_id].name
        a = _ps(ln, ev)
        b = a + ev.duration_ps
        return name == MARK or (name.startswith(HOST_PREFIXES)
                                and b > lo and a < hi)

    for h in host:
        p = copy_plane(h, lambda ln: True, host_event)
        for ln in p.lines:
            for ev in ln.events:
                if p.event_metadata[ev.metadata_id].name == MARK:
                    ev.offset_ps = lo - ln.timestamp_ns * 1000
                    ev.duration_ps = hi - lo
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("src")
    ap.add_argument("dst")
    ap.add_argument("--step", type=int, default=2)
    args = ap.parse_args(argv)
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    space = xplane_pb2.XSpace()
    with open(args.src, "rb") as f:
        space.ParseFromString(f.read())
    data = cut(space, args.step).SerializeToString()
    with gzip.open(args.dst, "wb") as f:
        f.write(data)
    print(f"{args.dst}: {len(data)} bytes, gzipped")


if __name__ == "__main__":
    main()
