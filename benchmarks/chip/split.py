"""The fused step's device time by stage, the program's host spans, and
device idle time by the program span that was open, from one traced run.

    python3 benchmarks/chip/split.py --workload edge8.bulk --seed 7 \
        --seconds 20 --trace 1

runs `run.py` with the same arguments; for a traced run it logs, before
the result line:

  split programs {program: [executions, mean device ms]}
  split stages  {"jit_step": ms per whole execution: busy, each stage,
                "other" (operations in no stage)}
  split spans   {span: [count, mean ms]} of the program's host spans
  split parked  100 x slots left out / (left out + stepped), summed over
                the `engine.step` spans' `parked` and `n`
  split idle    {span: ms}: each idle gap between device operations on
                the first chip, by the innermost program span open on
                the engine worker's thread ("none" where none was)

Stages are the `jax.named_scope`s of `serving/asr.py` `_step_fn`: an
operation belongs to the first stage named in its op name
("jit(step)/expand/while/body/..."), and a stage's time is the union of
its operations' intervals, so a loop is not counted twice with its body.
`jax.profiler.ProfileData` gives an event's own stats but not those of
its metadata, where a TPU trace may keep the op name, so the metadata
stats are read from the `.xplane.pb` here (`metadata_stats`).

The per-layer readers of `BENCHMARK.json` see `devtrace.Reduced`, which
keeps no op name and no event's thread or stats; this script reads the
same trace file before `run.py` removes it.
"""
from __future__ import annotations

import bisect
import json
import re
import struct
import sys
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

STAGES = ("mfcc", "tds_forward", "expand", "writeback")
SPAN_PREFIXES = ("worker.", "engine.", "asr.")
WORKER_SPAN = "worker.pump"     # marks the engine worker's thread
OP_NAME_STATS = ("tf_op", "op_name")


# -- the .xplane.pb's metadata stats ---------------------------------------
def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one protobuf message."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 1:
            val, i = buf[i:i + 8], i + 8
        elif wire == 2:
            n, i = _varint(buf, i)
            val, i = buf[i:i + n], i + n
        elif wire == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield num, wire, val


def _stat(buf, names: Dict[int, str]):
    """XStat -> (name, value); a ref value is an interned string."""
    key = val = None
    for num, _w, v in _fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            val = struct.unpack("<d", v)[0]
        elif num in (3, 7):
            val = v if num == 3 else names.get(v, "")
        elif num == 4:
            val = v - (1 << 64) if v >= 1 << 63 else v
        elif num == 5:
            val = bytes(v).decode("utf-8", "replace")
        elif num == 6:
            val = bytes(v)
    return names.get(key, str(key)), val


def metadata_stats(blob: bytes) -> Dict[str, Dict[str, dict]]:
    """{plane name: {event name: {stat: value}}} of the stats that a
    serialized XSpace keeps on its event metadata (XSpace.planes = 1;
    XPlane.name = 2, event_metadata = 4, stat_metadata = 5;
    XEventMetadata.name = 2, display_name = 4, stats = 5)."""
    out = {}
    for num, _w, plane in _fields(memoryview(blob)):
        if num != 1:
            continue
        name, events, names = "", [], {}
        for pnum, _w, v in _fields(plane):
            if pnum == 2:
                name = bytes(v).decode()
            elif pnum in (4, 5):
                entry = dict((f, x) for f, _w, x in _fields(v))
                if 2 not in entry:
                    continue
                if pnum == 4:
                    events.append(entry[2])
                else:
                    names[entry[1]] = next(
                        (bytes(x).decode() for f, _w, x in _fields(entry[2])
                         if f == 2), "")
        found = {}
        for ev in events:
            stats, labels = {}, []
            for f, _w, x in _fields(ev):
                if f in (2, 4):
                    labels.append(bytes(x).decode("utf-8", "replace"))
                elif f == 5:
                    k, val = _stat(x, names)
                    stats[k] = val
            if stats:
                for label in labels:
                    found[label] = stats
        out[name] = found
    return out


# -- device operations by stage --------------------------------------------
def op_name(stats: dict) -> str:
    """An operation's op name ("jit(step)/mfcc/mul"), from its stats."""
    for key in OP_NAME_STATS:
        if isinstance(stats.get(key), str) and stats[key]:
            return stats[key]
    for v in stats.values():
        m = re.search(r'op_name="([^"]*)"', v) if isinstance(v, str) \
            else None
        if m:
            return m.group(1)
    return ""


def stage(name: str) -> str:
    """The first stage named in an op name, or ""."""
    return next((p for p in name.split("/") if p in STAGES), "")


@dataclass
class Chip:
    ops: List[Tuple[float, float, str]]       # (start, end, stage), by start
    modules: List[Tuple[str, float, float]]   # (program, start, end)


def chips(data, meta: Dict[str, Dict[str, dict]]) -> List[Chip]:
    """Every TPU plane that ran an operation, times in seconds."""
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        kept = meta.get(plane.name, {})
        ops, mods = [], []
        for line in plane.lines:
            if line.name == "XLA Ops":
                for ev in line.events:
                    stats = dict(kept.get(ev.name, {}), **dict(ev.stats))
                    a = ev.start_ns * 1e-9
                    ops.append((a, a + ev.duration_ns * 1e-9,
                                stage(op_name(stats))))
            elif line.name == "XLA Modules":
                for ev in line.events:
                    a = ev.start_ns * 1e-9
                    mods.append((re.sub(r"\(\d+\)$", "", ev.name), a,
                                 a + ev.duration_ns * 1e-9))
        if ops:
            out.append(Chip(_inherit(sorted(ops)),
                            sorted(mods, key=lambda m: m[1])))
    return out


def _inherit(ops):
    """An operation with no stage of its own (a TPU trace gives a `while`
    no op name) takes the stage of the operations inside it, where they
    all have one and the same."""
    starts = [o[0] for o in ops]
    out = []
    for a, b, g in ops:
        if not g:
            inside = {o[2] for o in ops[bisect.bisect_right(starts, a):
                                        bisect.bisect_left(starts, b)]
                      if o[1] <= b}
            if len(inside) == 1:
                g = inside.pop()
        out.append((a, b, g))
    return out


def merged(spans) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(spans):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(b, out[-1][1]))
        else:
            out.append((a, b))
    return out


def union(spans) -> float:
    return sum(b - a for a, b in merged(spans))


def stage_ms(chip_list: List[Chip], program: str = "jit_step"
             ) -> Optional[dict]:
    """Mean device ms per execution of `program` that the trace holds
    whole (all but each chip's first and last): "busy" (the union of
    its operations), each stage's union, and "other", the busy time in
    which no operation of a stage runs."""
    tot: Dict[str, float] = defaultdict(float)
    n = 0
    for ch in chip_list:
        starts = [o[0] for o in ch.ops]
        for _p, a, b in [m for m in ch.modules if m[0] == program][1:-1]:
            i = bisect.bisect_left(starts, a)
            inside = []
            while i < len(ch.ops) and ch.ops[i][0] < b:
                inside.append(ch.ops[i])
                i += 1
            busy = union((s, e) for s, e, _ in inside)
            tot["busy"] += busy
            for name in STAGES:
                tot[name] += union((s, e) for s, e, g in inside
                                   if g == name)
            tot["other"] += busy - union((s, e) for s, e, g in inside if g)
            n += 1
    if not n:
        return None
    return {k: 1e3 * v / n for k, v in tot.items()}


def _table(intervals) -> dict:
    """{name: [count, mean ms]} of (name, start, end) intervals."""
    durs: Dict[str, list] = defaultdict(list)
    for name, a, b in intervals:
        durs[name].append(b - a)
    return {k: [len(v), 1e3 * sum(v) / len(v)]
            for k, v in sorted(durs.items())}


def program_table(chip_list: List[Chip]) -> dict:
    """{program: [executions, mean device ms]} over every chip."""
    return _table(m for ch in chip_list for m in ch.modules)


# -- host spans --------------------------------------------------------------
@dataclass
class Span:
    name: str
    start: float
    end: float
    stats: dict = field(default_factory=dict)


def threads(data) -> List[List[Span]]:
    """The host's lines (one per thread), each a list of its events."""
    out = []
    for plane in data.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                out.append([Span(ev.name, ev.start_ns * 1e-9,
                                 (ev.start_ns + ev.duration_ns) * 1e-9,
                                 dict(ev.stats)) for ev in line.events])
    return out


def program_spans(line: List[Span]) -> List[Span]:
    return [s for s in line if s.name.startswith(SPAN_PREFIXES)]


def span_table(lines: List[List[Span]]) -> dict:
    """{span: [count, mean ms]} over every thread."""
    return _table((s.name, s.start, s.end) for line in lines
                  for s in program_spans(line))


def parked_pct(lines: List[List[Span]]) -> Optional[float]:
    steps = [s.stats for line in lines for s in line
             if s.name == "engine.step" and "parked" in s.stats]
    parked = sum(s["parked"] for s in steps)
    total = parked + sum(s["n"] for s in steps)
    return 100.0 * parked / total if total else None


def worker_line(lines: List[List[Span]]) -> List[Span]:
    return next((line for line in lines
                 if any(s.name == WORKER_SPAN for s in line)), [])


def idle_by_span(chip: Chip, worker: List[Span]) -> Dict[str, float]:
    """{span: seconds} of the idle gaps between the chip's operations, by
    the innermost program span open on the worker's thread (the one that
    opened last of those open: spans on one thread nest)."""
    spans = sorted(program_spans(worker), key=lambda s: s.start)
    busy = merged((a, b) for a, b, _ in chip.ops)
    out: Dict[str, float] = defaultdict(float)
    j = 0
    for (_, a), (b, _) in zip(busy, busy[1:]):
        if b <= a:
            continue
        while j < len(spans) and spans[j].end <= a:
            j += 1
        live = []
        for s in spans[j:]:
            if s.start >= b:
                break
            if s.end > a:
                live.append(s)
        cuts = sorted({a, b} | {t for s in live for t in (s.start, s.end)
                                if a < t < b})
        for x, y in zip(cuts, cuts[1:]):
            open_ = [s for s in live if s.start <= x and s.end >= y]
            name = max(open_, key=lambda s: s.start).name if open_ \
                else "none"
            out[name] += y - x
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def report(data, meta) -> List[str]:
    """The lines that `main` logs for one trace."""
    chip_list = chips(data, meta)
    lines = threads(data)
    idle = idle_by_span(chip_list[0], worker_line(lines)) if chip_list \
        else {}
    return [f"split programs {json.dumps(program_table(chip_list))}",
            f"split stages {json.dumps({'jit_step': stage_ms(chip_list)})}",
            f"split spans {json.dumps(span_table(lines))}",
            f"split parked {parked_pct(lines)}",
            "split idle " + json.dumps({k: 1e3 * v
                                        for k, v in idle.items()})]


def main(argv=None) -> int:
    import devtrace
    import run

    def reduce_and_split(path: str, window_s: float):
        from jax.profiler import ProfileData

        with open(path, "rb") as f:
            blob = f.read()
        data = ProfileData.from_serialized_xspace(blob)
        for line in report(data, metadata_stats(blob)):
            run.log(line)
        return devtrace.reduce_profile(data, window_s)

    devtrace.reduce_trace = reduce_and_split
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
