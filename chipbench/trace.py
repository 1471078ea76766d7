"""From a profiler trace of one ``serve()`` call to the per-layer metrics.

``read_dir`` reads the ``.xplane.pb`` that ``jax.profiler.trace`` wrote:
the device's op events (the TPU plane's ``XLA Ops`` line), the host
threads' events, and the benchmark's own span around the call
(``CALL_SPAN``). ``per_layer`` hands a ``Run`` to each per-layer metric's
reader (``metrics/<name>.py``), which returns a number or None when it
finds nothing to read, and builds the breakdown: the device ops that took
most time, and the longest idle gaps with what the host was doing.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

from chipbench.shapes import short

CALL_SPAN = "chipbench.serve"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


@dataclasses.dataclass(frozen=True)
class Op:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Reading:
    """The parts of one trace the metrics read; times in ns on the
    profile's clock, seconds where the name says so."""
    ops: list                  # device ops inside the call span, by start
    modules: list              # device programs inside the call span
    host: dict                 # thread name -> [Op] inside the call span
    span: tuple                # (start_ns, end_ns) of CALL_SPAN
    chips: int

    @property
    def window_s(self) -> float:
        return (self.span[1] - self.span[0]) / 1e9

    @property
    def busy_s(self) -> float:
        return busy_ns(self.ops) / 1e9 / max(self.chips, 1)


def busy_ns(ops) -> float:
    """Length of the union of the ops' intervals."""
    total, end = 0.0, None
    for o in sorted(ops, key=lambda o: o.start_ns):
        if end is None or o.start_ns > end:
            total += o.dur_ns
            end = o.end_ns
        elif o.end_ns > end:
            total += o.end_ns - end
            end = o.end_ns
    return total


def _events(line):
    for e in line.events:
        yield Op(e.name, float(e.start_ns), float(e.duration_ns))


def read_file(path: str) -> Reading:
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    return reading_from_planes(
        [(pl.name, [(ln.name, list(_events(ln))) for ln in pl.lines])
         for pl in data.planes])


def read_dir(directory: str) -> Reading:
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {directory}, "
                           f"found {files}")
    return read_file(files[0])


def reading_from_planes(planes) -> Reading:
    """``planes``: [(plane name, [(line name, [Op])])], as read from the
    file or from a recorded JSON copy of it."""
    span, host, dev, mods, chips = None, {}, [], [], 0
    for pname, lines in planes:
        if DEVICE_PLANE.match(pname):
            chips += 1
            for lname, evs in lines:
                if lname == OPS_LINE:
                    dev.extend(evs)
                elif lname == MODULES_LINE:
                    mods.extend(evs)
        elif pname.startswith("/host:"):
            for lname, evs in lines:
                for o in evs:
                    if o.name == CALL_SPAN:
                        span = (o.start_ns, o.end_ns)
                host[lname] = evs
    if span is None:
        raise RuntimeError(f"no {CALL_SPAN!r} span in the trace")
    lo, hi = span

    def within(evs):
        return sorted((o for o in evs if o.start_ns >= lo and o.end_ns <= hi),
                      key=lambda o: o.start_ns)

    host = {k: [o for o in v if o.end_ns > lo and o.start_ns < hi]
            for k, v in host.items()}
    return Reading(ops=within(dev), modules=within(mods),
                   host={k: v for k, v in host.items() if v},
                   span=span, chips=chips)


@dataclasses.dataclass
class Run:
    """What a per-layer metric's reader is given."""
    cfg: dict
    mix: dict
    peaks: dict
    reading: Reading
    prompt_lens: list          # per request of the traced call
    served_lens: list          # tokens each request returned
    stats: dict                # the call's ServeEngine.serve_stats

    @property
    def window_s(self) -> float:
        return self.reading.window_s


def device_ops(reading: Reading):
    tot = collections.Counter()
    for o in reading.ops:
        tot[o.name] += o.dur_ns
    return [[short(name), ns / 1e9] for name, ns in tot.most_common(TOP)]


def caller(reading: Reading):
    """(name, events) of the host thread that made the call: the one
    holding the call's span."""
    return max(reading.host.items(), default=("", []),
               key=lambda kv: sum(1 for o in kv[1] if o.name == CALL_SPAN))


def idle_gaps(reading: Reading):
    """The longest stretches of the call with no op on the device, each
    named by the host event (innermost, longest overlap) that ran over it
    on the thread that made the call."""
    gaps, end = [], reading.span[0]
    for o in reading.ops:
        if o.start_ns > end:
            gaps.append((end, o.start_ns))
        end = max(end, o.end_ns)
    if reading.span[1] > end:
        gaps.append((end, reading.span[1]))
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    host = caller(reading)[1]
    out = []
    for lo, hi in gaps[:TOP]:
        best, score = "host: no event", 0.0
        for o in host:
            if o.name == CALL_SPAN:
                continue
            ov = min(hi, o.end_ns) - max(lo, o.start_ns)
            # prefer the event that covers the gap most, then the shortest
            key = ov - 1e-9 * o.dur_ns
            if ov > 0 and key > score:
                best, score = o.name, key
        out.append([best, (hi - lo) / 1e9])
    return out


def make_run(cell, reading: Reading, prompt_lens, served_lens,
             stats: dict) -> Run:
    from chipbench.peaks import peaks
    return Run(cfg=cell.cfg, mix=cell.mix, peaks=peaks(cell.device_kind),
               reading=reading, prompt_lens=list(prompt_lens),
               served_lens=list(served_lens), stats=stats)


def metrics_of(cell, run: Run) -> dict:
    out = {}
    for m in cell.per_layer:
        value = cell.metric_reader(m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def per_layer(cell, reading: Reading, call, stats: dict):
    """The cell's per-layer metrics and the breakdown of one traced call."""
    run = make_run(cell, reading, [len(p) for p in call.prompts],
                   [len(o) for o in call.outputs], stats)
    return metrics_of(cell, run), {"device_ops": device_ops(reading),
                                   "idle_gaps": idle_gaps(reading)}


# -- a recorded reading, for the tests of this reduction --------------------

def dump(path: str, cell, reading: Reading, run: Run, metrics: dict,
         breakdown: dict) -> None:
    """Write a reading with the inputs and the numbers read from it."""
    import gzip
    import json
    names, index = [], {}

    def ev(o):
        if o.name not in index:
            index[o.name] = len(names)
            names.append(o.name)
        return [index[o.name], o.start_ns, o.dur_ns]

    line, host = caller(reading)
    rec = {"cell": cell.name, "device_kind": cell.device_kind,
           "span": list(reading.span), "chips": reading.chips,
           "ops": [ev(o) for o in reading.ops],
           "modules": [ev(o) for o in reading.modules],
           "host": {line: [ev(o) for o in host]},
           "prompt_lens": run.prompt_lens, "served_lens": run.served_lens,
           "stats": run.stats, "metrics": metrics, "breakdown": breakdown}
    rec["names"] = names
    with gzip.open(path, "wt") as f:
        json.dump(rec, f)


def load(path: str):
    """(reading, recorded record) of a file ``dump`` wrote."""
    import gzip
    import json
    with gzip.open(path, "rt") as f:
        rec = json.load(f)
    names = rec["names"]

    def evs(rows):
        return [Op(names[i], s, d) for i, s, d in rows]

    reading = Reading(ops=evs(rec["ops"]), modules=evs(rec["modules"]),
                      host={k: evs(v) for k, v in rec["host"].items()},
                      span=tuple(rec["span"]), chips=rec["chips"])
    return reading, rec
