"""The stage split, the host spans and the idle time by span, on a small
recorded trace in the layout a TPU trace has: op names (`tf_op`) on the
ops' event metadata, as interned strings or plain ones, a `while` with
no op name around its body, and nested program spans with their
arguments on the engine worker's thread.  Times in microseconds."""
from pathlib import Path
from types import SimpleNamespace

import pytest

import devtrace
import split
from run import load_module

US = 1_000_000          # picoseconds per microsecond
STAT = {"tf_op": 9, "n": 10, "w": 11, "parked": 12, "b": 13, "sid": 14}


def ev(meta, start_us, dur_us, **stats):
    st = " ".join(f"stats {{ metadata_id: {STAT[k]} int64_value: {v} }}"
                  for k, v in stats.items())
    return (f"events {{ metadata_id: {meta} offset_ps: {start_us * US} "
            f"duration_ps: {dur_us * US} {st} }}")


def meta(i, name, tf_op=None, ref=None):
    st = (f'stats {{ metadata_id: 9 str_value: "{tf_op}" }}' if tf_op
          else f"stats {{ metadata_id: 9 ref_value: {ref} }}" if ref
          else "")
    return (f'event_metadata {{ key: {i} value {{ id: {i} name: "{name}" '
            f"{st} }} }}")


def stat_names():
    names = [f'stat_metadata {{ key: {i} value {{ id: {i} name: "{k}" }} }}'
             for k, i in STAT.items()]
    names.append('stat_metadata { key: 30 value { id: 30 name: '
                 '"jit(step)/expand/while/body/pallas_call:" } }')
    return " ".join(names)


# three executions of the step; the middle one is whole:
#   [20,22) mfcc, [22,26) tds_forward, [26,27) a gather in no stage,
#   [27,37) the while (no op name) around [28,31) and [32,35) of expand,
#   [37,39) writeback
# the worker: a pump round with a step (assemble, dispatch), the watchers,
# the command wait, a client's push, and a round with one harvest
XSPACE = f"""
planes {{
  id: 1
  name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0
    {ev(1, 0, 10)} {ev(1, 20, 20)} {ev(1, 60, 10)} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0
    {ev(2, 0, 5)} {ev(2, 20, 2)} {ev(3, 22, 4)} {ev(4, 26, 1)}
    {ev(5, 27, 10)} {ev(6, 28, 3)} {ev(7, 32, 3)} {ev(8, 37, 2)}
    {ev(2, 60, 5)} }}
  {meta(1, "jit_step(5)")}
  {meta(2, "%fusion.1 = f32[2,8] fusion()", "jit(step)/mfcc/mul:")}
  {meta(3, "%tds_conv.2 = f32[2,16,15,80] custom-call()",
        "jit(step)/tds_forward/pallas_call:")}
  {meta(4, "%gather.3 = f32[2] gather()", "jit(step)/gather:")}
  {meta(5, "%while.4 = (s32[]) while()")}
  {meta(6, "%fusion.5 = f32[2] fusion()",
        "jit(step)/expand/while/body/gather:")}
  {meta(7, "%hypothesis_unit.6 = (s32[2,128]) custom-call()", ref=30)}
  {meta(8, "%fusion.7 = f32[2] fusion()", "jit(step)/writeback/scatter:")}
  {stat_names()}
}}
planes {{
  id: 2
  name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
    {ev(1, 4, 34)} {ev(2, 6, 12, n=2, w=4, parked=2)}
    {ev(3, 7, 5, b=2, w=4)} {ev(4, 12, 5)}
    {ev(5, 38, 3)} {ev(6, 41, 9)} {ev(7, 50, 8)} {ev(8, 50, 8)}
    {ev(9, 51, 6, sid=3)}
    {ev(1, 58, 8)} {ev(10, 59, 6, sid=1)} {ev(11, 60, 3)}
    {ev(2, 65, 1, n=4, w=1, parked=0)} }}
  lines {{ id: 2 name: "python" timestamp_ns: 0 {ev(12, 0, 70)} }}
  {meta(1, "worker.pump")} {meta(2, "engine.step")}
  {meta(3, "asr.assemble")} {meta(4, "asr.dispatch")}
  {meta(5, "worker.resolve")} {meta(6, "worker.wait")}
  {meta(7, "worker.exec")} {meta(8, "bench.submit")}
  {meta(9, "engine.push")} {meta(10, "engine.harvest")}
  {meta(11, "asr.readout")} {meta(12, "bench.wait")}
  {stat_names()}
}}
"""


@pytest.fixture(scope="module")
def trace():
    from jax.profiler import ProfileData
    blob = ProfileData.text_proto_to_serialized_xspace(XSPACE)
    return ProfileData.from_serialized_xspace(blob), \
        split.metadata_stats(blob)


def test_metadata_stats_plain_and_interned(trace):
    _data, meta = trace
    ops = meta["/device:TPU:0"]
    assert ops["%fusion.1 = f32[2,8] fusion()"] == {
        "tf_op": "jit(step)/mfcc/mul:"}
    assert ops["%hypothesis_unit.6 = (s32[2,128]) custom-call()"] == {
        "tf_op": "jit(step)/expand/while/body/pallas_call:"}
    assert "%while.4 = (s32[]) while()" not in ops


def test_op_names_and_stages():
    assert split.op_name({"tf_op": "jit(step)/mfcc/mul:"}) == \
        "jit(step)/mfcc/mul:"
    assert split.op_name({"long_name": '%f = f32[] fusion(), metadata='
                          '{op_name="jit(step)/expand/x"}'}) == \
        "jit(step)/expand/x"
    assert split.op_name({"hlo_category": "while"}) == ""
    assert split.stage("jit(step)/expand/while/body/gather:") == "expand"
    assert split.stage("jit(step)/expand_step/gather:") == ""
    assert split.stage("jit(step)/gather:") == ""


def test_stage_split_counts_a_loop_once(trace):
    chips = split.chips(*trace)
    assert len(chips) == 1
    ms = split.stage_ms(chips)
    # the middle execution alone; the while takes its body's stage, so
    # expand is its 10 us, not 10 + 6
    assert ms == pytest.approx({"busy": 19e-3, "mfcc": 2e-3,
                                "tds_forward": 4e-3, "expand": 10e-3,
                                "writeback": 2e-3, "other": 1e-3})
    assert split.stage_ms(chips, "jit_readout") is None
    assert split.program_table(chips) == {
        "jit_step": [3, pytest.approx(40e-3 / 3)]}


def test_spans_and_parked(trace):
    lines = split.threads(trace[0])
    table = split.span_table(lines)
    assert table["worker.pump"] == [2, pytest.approx(21e-3)]
    assert table["asr.assemble"] == [1, pytest.approx(5e-3)]
    assert table["engine.harvest"] == [1, pytest.approx(6e-3)]
    assert "bench.submit" not in table and "bench.wait" not in table
    # 2 parked beside 2 stepped, then none beside 4
    assert split.parked_pct(lines) == pytest.approx(25.0)


def test_idle_by_innermost_program_span(trace):
    data, meta = trace
    chip, = split.chips(data, meta)
    idle = split.idle_by_span(chip, split.worker_line(split.threads(data)))
    # gaps [5, 20) and [39, 60); the client's annotation is no program
    # span, so its push counts under engine.push and the rest of the
    # command under worker.exec
    want = {"worker.pump": 4, "engine.step": 2, "asr.assemble": 5,
            "asr.dispatch": 5, "worker.resolve": 2, "worker.wait": 9,
            "worker.exec": 2, "engine.push": 6, "engine.harvest": 1}
    assert idle == pytest.approx({k: v * 1e-6 for k, v in want.items()})
    assert sum(idle.values()) == pytest.approx(36e-6)


def test_report_lines(trace):
    lines = split.report(*trace)
    assert [ln.split(" ", 2)[1] for ln in lines] == [
        "programs", "stages", "spans", "parked", "idle"]


@pytest.mark.parametrize("name,ms", [("assemble_host_ms.bulk", 5e-3),
                                     ("harvest_host_ms.bulk", 6e-3)])
def test_host_span_readers(trace, name, ms):
    reader = load_module(Path(split.__file__).parent / "metrics"
                         / f"{name}.py")
    red = devtrace.reduce_profile(trace[0], 70e-6)
    assert reader.read(SimpleNamespace(trace=red)) == pytest.approx(ms)
    # no chip ran the steps, or no trace: nothing to read
    no_chip = devtrace.Reduced(70e-6, 0, [], [], red.host)
    assert reader.read(SimpleNamespace(trace=no_chip)) is None
    assert reader.read(SimpleNamespace(trace=None)) is None
    # a program without the span (the parent of the spans) reads nothing
    bare = devtrace.Reduced(red.window_s, red.chips, red.ops, red.modules,
                            [e for e in red.host if e.name != reader.SPAN])
    assert reader.read(SimpleNamespace(trace=bare)) is None
