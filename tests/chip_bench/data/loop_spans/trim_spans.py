"""Cut a profiler capture (XSpace protobuf) down to what
benchmarks/chip/lib/spans.py reads, over a time slice: the device plane's
programs and operations (names and times; of the operations' metadata only
the ``tf_op`` stat, which holds the ``jax.named_scope`` path) and the
host planes' ``pstpu.*`` annotations WITH their attributes. Uses
../trim_xplane.py's wire-format walk. Not used by the benchmark or its tests.

    python trim_spans.py <in.xplane.pb> <out.xplane.pb> <lo_s> <hi_s>

``lo_s`` / ``hi_s`` are seconds after the capture's first device event; an
event is kept when it STARTS inside the slice, so a dispatch the slice
cuts keeps a part of its spans only."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from trim_xplane import enc, fields, get  # noqa: E402

KEEP_DEVICE_LINES = ("XLA Modules", "XLA Ops")


def plane_maps(plane):
    stat_names, event_names = {}, {}
    for n, w, v in plane:
        if n == 5:
            e = fields(v)
            stat_names[get(e, 1)] = get(fields(get(e, 2, b"")), 2,
                                        b"").decode()
        elif n == 4:
            e = fields(v)
            event_names[get(e, 1)] = get(fields(get(e, 2, b"")), 2,
                                         b"").decode(errors="replace")
    return stat_names, event_names


def event_start_s(line_ts_ns, ev):
    return line_ts_ns * 1e-9 + get(ev, 2, 0) * 1e-12


def first_device_event_s(space):
    best = None
    for num, wt, v in space:
        if num != 1:
            continue
        plane = fields(v)
        if not get(plane, 2, b"").decode().startswith("/device:TPU:0"):
            continue
        for n, w, raw in plane:
            if n != 3:
                continue
            line = fields(raw)
            ts = get(line, 3, 0)
            for n3, w3, v3 in line:
                if n3 == 4:
                    s = event_start_s(ts, fields(v3))
                    best = s if best is None else min(best, s)
    return best


def trim(path, out_path, lo_s, hi_s):
    space = fields(open(path, "rb").read())
    base = first_device_event_s(space)
    out_space = []
    for num, wt, v in space:
        if num != 1:
            out_space.append((num, wt, v))
            continue
        plane = fields(v)
        name = get(plane, 2, b"").decode()
        device = name.startswith("/device:TPU:0")
        if not device and not name.startswith("/host:CPU"):
            continue
        stat_names, event_names = plane_maps(plane)
        used_events, used_stats = set(), set()
        new_lines = []
        for n, w, raw in plane:
            if n != 3:
                continue
            line = fields(raw)
            if device and get(line, 2, b"").decode() not in KEEP_DEVICE_LINES:
                continue
            ts = get(line, 3, 0)
            kept, any_event = [], False
            for n3, w3, v3 in line:
                if n3 != 4:
                    kept.append((n3, w3, v3))
                    continue
                ev = fields(v3)
                if not lo_s <= event_start_s(ts, ev) - base < hi_s:
                    continue
                if device:
                    ev = [f for f in ev if f[0] != 4]       # no event stats
                elif not event_names.get(get(ev, 1), "").startswith("pstpu."):
                    continue
                else:
                    for f in ev:
                        if f[0] == 4:
                            st = fields(f[2])
                            used_stats.add(get(st, 1))
                            if any(x[0] == 7 for x in st):
                                used_stats.add(get(st, 7))
                used_events.add(get(ev, 1))
                kept.append((4, 2, enc(ev)))
                any_event = True
            if any_event:
                new_lines.append((3, 2, enc(kept)))
        new_plane = []
        tf_op_ids = {i for i, s in stat_names.items() if s == "tf_op"}
        for n, w, raw in plane:
            if n == 3 or n == 6:
                continue
            if n == 4:
                e = fields(raw)
                if get(e, 1) not in used_events:
                    continue
                md = []
                for f in fields(get(e, 2, b"")):
                    if f[0] in (1, 2, 4):
                        md.append(f)
                    elif f[0] == 5 and get(fields(f[2]), 1) in tf_op_ids:
                        md.append(f)
                        st = fields(f[2])
                        used_stats.add(get(st, 1))
                        if any(x[0] == 7 for x in st):
                            used_stats.add(get(st, 7))
                new_plane.append((4, 2, enc([(1, 0, get(e, 1)),
                                             (2, 2, enc(md))])))
            elif n == 5:
                if get(fields(raw), 1) in used_stats:
                    new_plane.append((n, w, raw))
            else:
                new_plane.append((n, w, raw))
        out_space.append((1, 2, enc(new_plane + new_lines)))
    with open(out_path, "wb") as f:
        f.write(enc(out_space))


if __name__ == "__main__":
    trim(sys.argv[1], sys.argv[2], float(sys.argv[3]), float(sys.argv[4]))
