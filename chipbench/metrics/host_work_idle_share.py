"""host_work_idle_share (scheduler, ``serve/engine.py``
``_serve_loop_packed``): share of the traced call's wall time in which the
device was idle while the serving loop did host work. The device-idle
time inside the call's span (no op running, as in ``device_idle_share``)
that the union of the loop's ``serve.host.*`` spans covers, on the thread
that made the call, over the call's wall time. So at most
``device_idle_share``; the rest of the idle time falls in the loop's
waits on the device (``serve.sync.*``) or in no span of the loop."""
from chipbench import trace

HOST = "serve.host."
SYNC = "serve.sync."


def _union(intervals):
    out = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return out


def _overlap_ns(a, b) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0.0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def read(run):
    r = run.reading
    lo, hi = r.span
    spans = [o for o in trace.caller(r)[1]
             if o.name.startswith((HOST, SYNC))]
    if (hi <= lo or not r.ops
            or not any(o.name.startswith(HOST) for o in spans)):
        return None
    busy = _union((max(o.start_ns, lo), min(o.end_ns, hi)) for o in r.ops)
    idle = _union((a[1], b[0]) for a, b in zip(
        [[lo, lo]] + busy, busy + [[hi, hi]]) if b[0] > a[1])

    def idle_under(names):
        return _overlap_ns(idle, _union(
            (max(o.start_ns, lo), min(o.end_ns, hi)) for o in spans
            if o.name in names))

    names = sorted({o.name for o in spans})
    host = idle_under({n for n in names if n.startswith(HOST)})
    idle_ns = sum(b - a for a, b in idle)
    parts = [f"{n} {idle_under({n}) / 1e6!r}" for n in names]
    parts.append(f"no span {(idle_ns - idle_under(set(names))) / 1e6!r}")
    print(f"host_work_idle_share: device idle {idle_ns / 1e6!r} ms of "
          f"{(hi - lo) / 1e6!r} ms, {host / 1e6!r} ms under serve.host.*; "
          f"idle ms by span: " + ", ".join(parts), flush=True)
    return 100.0 * host / (hi - lo)
