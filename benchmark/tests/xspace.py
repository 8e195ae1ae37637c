"""A hand encoder of the profiler's XSpace protobuf, enough to build a
trace whose reduction can be worked out by hand (field numbers from
tsl/profiler/protobuf/xplane.proto)."""


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | 0x80 if n else b)
        if not n:
            return bytes(out)


def _int(num: int, v: int) -> bytes:
    return _varint(num << 3) + _varint(v)


def _bytes(num: int, b: bytes) -> bytes:
    return _varint((num << 3) | 2) + _varint(len(b)) + b


def xspace(planes) -> bytes:
    """``[(plane, [(line, [(event, start_ns, dur_ns), ...]), ...]), ...]``"""
    out = b""
    for pi, (pname, lines) in enumerate(planes):
        names = sorted({e[0] for _ln, evs in lines for e in evs})
        ids = {n: i + 1 for i, n in enumerate(names)}
        body = _int(1, pi + 1) + _bytes(2, pname.encode())
        for li, (lname, evs) in enumerate(lines):
            lb = _int(1, li + 1) + _bytes(2, lname.encode()) + _int(3, 0)
            for n, s, d in evs:
                lb += _bytes(4, _int(1, ids[n]) + _int(2, s * 1000)
                             + _int(3, d * 1000))
            body += _bytes(3, lb)
        for n, i in ids.items():
            body += _bytes(4, _int(1, i) + _bytes(
                2, _int(1, i) + _bytes(2, n.encode())))
        out += _bytes(1, body)
    return out
