"""Profiler capture and the reduction of its trace to the numbers the
per-layer readers take.

`Tracer` wraps `jax.profiler.start_trace`/`stop_trace` into a directory
and times the traced window on the host clock.  `reduce_trace` reads the
`.xplane.pb` with `jax.profiler.ProfileData` (nothing but JAX):

* device planes: every `/device:TPU:<n>` plane that ran an operation;
  its "XLA Ops" line gives each operation's interval, its "XLA Modules"
  line each program execution (a jitted function's run);
* busy: the union of operation intervals on each chip, averaged over
  the chips used; idle = window - busy;
* kernels: operations are named by their HLO instruction ("%tds_conv.18 =
  f32[...] custom-call(...)"); a Mosaic kernel's custom call carries the
  kernel's name, so an instruction named `<kernel>.<n>` is that kernel;
* programs: "jit_step(<fingerprint>)" is one execution of the fused
  step, whatever its (slot bucket, window bucket) entry; the kernel
  calls that run inside an execution's interval are its calls, and each
  call's output shape is read from its instruction text;
* host: every event on host threads (`TraceAnnotation`s of the benchmark
  and JAX's own dispatch events), to say what the host did in each
  device idle gap.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import time
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

KERNELS = ("tds_conv", "hypothesis_unit", "layernorm", "logmel",
           "int8_matmul")


class Tracer:
    """One traced span of a run, written under `out_dir`."""

    def __init__(self, out_dir: str):
        self.out_dir = out_dir
        self.t0 = self.t1 = None

    def start(self) -> None:
        import jax
        # no Python function tracer: it slows the host many times over
        # and fills the trace; TraceMe events (annotations, dispatch) stay
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        self.t0 = time.monotonic()

    def stop(self) -> None:
        import jax
        self.t1 = time.monotonic()
        jax.profiler.stop_trace()

    @property
    def window_s(self) -> Optional[float]:
        return None if self.t1 is None else self.t1 - self.t0

    def xplane(self) -> Optional[str]:
        found = sorted(glob.glob(os.path.join(
            self.out_dir, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found else None


@dataclass
class Event:
    name: str             # instruction or program name, suffix stripped
    start: float          # seconds, on the trace's clock
    dur: float            # seconds
    label: str = ""       # kernel name, or "" for other operations
    shape: Tuple[int, ...] = ()   # a kernel call's (first) output shape

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class Execution:
    """One execution of a program on one chip, with the kernel calls that
    ran inside it: {kernel: [(seconds, output shape)]}."""
    name: str
    start: float
    dur: float
    calls: Dict[str, List[Tuple[float, Tuple[int, ...]]]]


@dataclass
class Reduced:
    window_s: float
    chips: int
    ops: List[List[Event]]               # per chip, by start
    modules: List[List[Event]]           # per chip, by start
    host: List[Event] = field(default_factory=list)

    # -- device time ----------------------------------------------------
    def busy_s(self) -> float:
        """Union of operation intervals, averaged over the chips used."""
        if not self.chips:
            return 0.0
        return sum(_union(ev) for ev in self.ops) / self.chips

    def idle_share(self) -> Optional[float]:
        if not self.chips or self.window_s <= 0:
            return None
        return max(0.0, 1.0 - self.busy_s() / self.window_s)

    def module_time(self, program: str) -> Tuple[float, int]:
        """(summed device seconds, executions) of one program (e.g.
        "jit_step")."""
        evs = [e for ch in self.modules for e in ch if e.name == program]
        return sum(e.dur for e in evs), len(evs)

    def executions(self, program: str) -> List[Execution]:
        """The executions of `program` that the trace holds whole, on
        every chip, with the Mosaic kernel calls inside each interval:
        all but the first and the last on each chip, which the traced
        span's edges may cut."""
        out = []
        for ops, mods in zip(self.ops, self.modules):
            starts = [e.start for e in ops]
            for m in [m for m in mods if m.name == program][1:-1]:
                calls: Dict[str, list] = defaultdict(list)
                i = bisect.bisect_left(starts, m.start)
                while i < len(ops) and ops[i].start < m.end:
                    if ops[i].label:
                        calls[ops[i].label].append((ops[i].dur,
                                                    ops[i].shape))
                    i += 1
                out.append(Execution(m.name, m.start, m.dur, dict(calls)))
        return out

    def kernel_shapes(self) -> list:
        """[[kernel, output shape, calls]] over the trace, most calls
        first."""
        seen: Dict[Tuple[str, Tuple[int, ...]], int] = defaultdict(int)
        for ch in self.ops:
            for e in ch:
                if e.label:
                    seen[e.label, e.shape] += 1
        return [[k, list(s), n] for (k, s), n in
                sorted(seen.items(), key=lambda kv: -kv[1])]

    def top_ops(self, n: int = 10) -> list:
        """[[name, seconds]] of the device operations that took most
        time: kernels under their own names, other instructions as
        "<instruction> in <program>" (a loop counts its body too)."""
        tot: Dict[str, float] = defaultdict(float)
        for ops, mods in zip(self.ops, self.modules):
            starts = [m.start for m in mods]
            for e in ops:
                if e.label:
                    tot[e.label] += e.dur
                    continue
                i = bisect.bisect_right(starts, e.start) - 1
                prog = mods[i].name if i >= 0 and e.start < mods[i].end \
                    else "?"
                tot[f"{e.name} in {prog}"] += e.dur
        return [[k, v] for k, v in sorted(tot.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> list:
        """[[what the host was doing, seconds]] of the longest gaps
        between device operations on the first chip used: the host event
        that overlaps the gap most, innermost first on ties."""
        if not self.chips:
            return []
        gaps = []
        busy = _merged(self.ops[0])
        for (_, a), (b, _) in zip(busy, busy[1:]):
            if b > a:
                gaps.append((a, b))
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for a, b in gaps[:n]:
            best, best_ov, best_dur = "no host event", 0.0, float("inf")
            for e in self.host:
                ov = min(b, e.end) - max(a, e.start)
                if ov > best_ov or (ov == best_ov and ov > 0
                                    and e.dur < best_dur):
                    best, best_ov, best_dur = e.name, ov, e.dur
            out.append([best, b - a])
        return out


def _merged(events: List[Event]) -> List[Tuple[float, float]]:
    spans = sorted((e.start, e.end) for e in events)
    out: List[Tuple[float, float]] = []
    for a, b in spans:
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _union(events: List[Event]) -> float:
    return sum(b - a for a, b in _merged(events))


def instruction(name: str) -> str:
    """"%tds_conv.18 = f32[...] custom-call(...)" -> "tds_conv.18"."""
    return name.split(" ", 1)[0].lstrip("%")


def kernel_label(inst: str) -> str:
    """The Mosaic kernel an instruction runs, or ""."""
    base = re.sub(r"\.\d+$", "", inst)
    return base if base in KERNELS else ""


def output_shape(name: str) -> Tuple[int, ...]:
    """"%tds_conv.1 = f32[8,32,15,80]{3,2,1,0} custom-call(...)" ->
    (8, 32, 15, 80); the first element of a tuple result; () where the
    name carries no shape."""
    m = re.search(r"= \(?[a-z]+[0-9]*\[([0-9,]*)\]", name)
    if not m:
        return ()
    return tuple(int(d) for d in m.group(1).split(",") if d)


def _call_shape(ev) -> Tuple[int, ...]:
    """A kernel call's output shape, from its name or else from the first
    of its text stats that holds the instruction."""
    shape = output_shape(ev.name)
    if not shape:
        for _key, value in ev.stats:
            if isinstance(value, str) and " = " in value:
                shape = output_shape(value)
                if shape:
                    break
    return shape


def program(name: str) -> str:
    """"jit_step(4387975846547272356)" -> "jit_step"."""
    return re.sub(r"\(\d+\)$", "", name)


def reduce_trace(path: str, window_s: float) -> Reduced:
    """Reduce one `.xplane.pb` to device and host intervals."""
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path), window_s)


def reduce_profile(data, window_s: float) -> Reduced:
    """Reduce a `jax.profiler.ProfileData` (see `reduce_trace`)."""
    ops, modules, host = [], [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            o, m = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        inst = instruction(ev.name)
                        label = kernel_label(inst)
                        o.append(Event(inst, ev.start_ns * 1e-9,
                                       ev.duration_ns * 1e-9, label,
                                       _call_shape(ev) if label else ()))
                elif line.name == "XLA Modules":
                    m += [Event(program(ev.name), ev.start_ns * 1e-9,
                                ev.duration_ns * 1e-9) for ev in line.events]
            if o:
                ops.append(sorted(o, key=lambda e: e.start))
                modules.append(sorted(m, key=lambda e: e.start))
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host += [Event(ev.name, ev.start_ns * 1e-9,
                               ev.duration_ns * 1e-9) for ev in line.events]
    return Reduced(window_s=window_s, chips=len(ops), ops=ops,
                   modules=modules, host=host)
