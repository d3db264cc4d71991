"""Cut a profiler capture (XSpace protobuf) down to the events of a time
slice of the device planes and host lines, and to the metadata they name.
Generic wire-format walk: fields this does not know are kept as they are."""
import sys

def varint(buf, i):
    shift = val = 0
    while True:
        b = buf[i]; i += 1
        val |= (b & 0x7F) << shift
        if not b & 0x80:
            return val, i
        shift += 7

def enc_varint(v):
    out = bytearray()
    while True:
        b = v & 0x7F; v >>= 7
        out.append(b | (0x80 if v else 0))
        if not v:
            return bytes(out)

def fields(buf):
    i, out = 0, []
    while i < len(buf):
        key, i = varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            v, i = varint(buf, i); out.append((num, wt, v))
        elif wt == 1:
            out.append((num, wt, buf[i:i+8])); i += 8
        elif wt == 2:
            n, i = varint(buf, i); out.append((num, wt, buf[i:i+n])); i += n
        elif wt == 5:
            out.append((num, wt, buf[i:i+4])); i += 4
        else:
            raise ValueError(wt)
    return out

def enc(fs):
    out = bytearray()
    for num, wt, v in fs:
        out += enc_varint(num << 3 | wt)
        if wt == 0: out += enc_varint(v)
        elif wt == 2: out += enc_varint(len(v)) + v
        else: out += v
    return bytes(out)

def get(fs, num, default=0):
    for n, wt, v in fs:
        if n == num: return v
    return default

def trim(path, out_path, lo_s, hi_s, keep_planes, host_lines):
    space = fields(open(path, "rb").read())
    new_space = []
    for num, wt, v in space:
        if num != 1:
            new_space.append((num, wt, v)); continue
        plane = fields(v)
        name = get(plane, 2, b"").decode()
        if not any(name.startswith(p) for p in keep_planes):
            continue
        used_events, used_stats = set(), set()
        new_plane, lines = [], []
        for n2, w2, v2 in plane:
            if n2 == 3: lines.append(fields(v2))
        t_lo = None
        for line in lines:
            ts = get(line, 3, 0)          # timestamp_ns
            lname = get(line, 2, b"").decode()
            if name.startswith("/host") and not any(h in lname for h in host_lines):
                line[:] = [f for f in line if f[0] != 4]
                continue
            kept = []
            for n3, w3, v3 in line:
                if n3 != 4:
                    kept.append((n3, w3, v3)); continue
                ev = fields(v3)
                start = ts * 1e-9 + get(ev, 2, 0) * 1e-12
                if lo_s <= start - BASE[0] < hi_s:
                    kept.append((n3, w3, enc([f for f in ev if f[0] != 4])))
                    used_events.add(get(ev, 1))
            line[:] = kept
        li = iter(lines)
        for n2, w2, v2 in plane:
            if n2 == 3:
                line = next(li)
                if any(f[0] == 4 for f in line):
                    new_plane.append((3, 2, enc(line)))
            elif n2 == 4:      # event_metadata map entry: key=1, value=2
                e = fields(v2)
                if get(e, 1) in used_events:
                    # drop stats inside the metadata (huge HLO protos) but keep id/name
                    md = [f for f in fields(get(e, 2, b"")) if f[0] in (1, 2, 4)]
                    new_plane.append((4, 2, enc([(1, 0, get(e, 1)), (2, 2, enc(md))])))
            elif n2 == 5:
                e = fields(v2)
                if get(e, 1) in used_stats:
                    new_plane.append((n2, w2, v2))
            elif n2 == 6:
                continue
            else:
                new_plane.append((n2, w2, v2))
        new_space.append((1, 2, enc(new_plane)))
    open(out_path, "wb").write(enc(new_space))

BASE = [0.0]
if __name__ == "__main__":
    path, out, base, lo, hi = sys.argv[1], sys.argv[2], float(sys.argv[3]), float(sys.argv[4]), float(sys.argv[5])
    BASE[0] = base
    trim(path, out, lo, hi, ("/device:TPU:0", "/host:CPU"), ("python3",))
