"""The trace reduction on a small recorded trace (written out as an
XSpace text proto, in the layout the TPU profiler writes: a device plane
with "XLA Modules" and "XLA Ops" lines, ops named by their HLO
instruction, and a host plane of TraceMe events)."""
import pytest

import devtrace as tr

US = 1_000_000          # picoseconds per microsecond


def ev(meta, start_us, dur_us):
    return (f"events {{ metadata_id: {meta} offset_ps: {start_us * US} "
            f"duration_ps: {dur_us * US} }}")


def meta(i, name):
    return f'event_metadata {{ key: {i} value {{ id: {i} name: "{name}" }} }}'


XSPACE = f"""
planes {{
  id: 1
  name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0
    {ev(1, 0, 10)} {ev(2, 30, 10)} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0
    {ev(3, 0, 4)} {ev(4, 2, 6)} {ev(5, 30, 5)} {ev(6, 36, 4)} }}
  lines {{ id: 3 name: "Async XLA Ops" timestamp_ns: 0 {ev(7, 0, 45)} }}
  {meta(1, "jit_step(111)")}
  {meta(2, "jit_step(222)")}
  {meta(3, "%tds_conv.1 = f32[8,32,15,80] custom-call(f32[8,40,1,80] %a)")}
  {meta(4, "%fusion.2 = f32[8,1520] fusion(f32[8,1520] %b)")}
  {meta(5, "%hypothesis_unit.3 = (s32[8,128]) custom-call(u32[8,8320] %c)")}
  {meta(6, "%while.4 = (s32[]) while(s32[] %d)")}
  {meta(7, "%copy-start.5 = (f32[1]) copy-start(f32[1] %e)")}
}}
planes {{ id: 2 name: "/device:TPU:1" }}
planes {{
  id: 3
  name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0 {ev(1, 5, 30)} {ev(2, 20, 12)} }}
  {meta(1, "bench.pump")}
  {meta(2, "PjitFunction(step)")}
}}
"""


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData
    return tr.reduce_profile(ProfileData.from_text_proto(XSPACE), 50e-6)


def test_busy_is_the_union_of_ops_on_chips_that_ran_them(reduced):
    # [0, 8) + [30, 35) + [36, 40) us; the async copy is not compute,
    # and TPU:1 ran nothing
    assert reduced.chips == 1
    assert reduced.busy_s() == pytest.approx(17e-6)
    assert reduced.idle_share() == pytest.approx(1 - 17 / 50)


def test_kernels_and_programs(reduced):
    assert reduced.kernel_shapes() == [["tds_conv", [8, 32, 15, 80], 1],
                                       ["hypothesis_unit", [8, 128], 1]]
    assert reduced.module_time("jit_step") == (pytest.approx(20e-6), 2)


def test_breakdown(reduced):
    top = reduced.top_ops(3)
    assert [k for k, _ in top] == ["fusion.2 in jit_step", "hypothesis_unit",
                                   "tds_conv"]
    assert top[0][1] == pytest.approx(6e-6)
    gaps = reduced.idle_gaps()
    # [8, 30): the pump annotation overlaps all 22 us, the dispatch 10;
    # [35, 36): no host event
    assert [g[0] for g in gaps] == ["bench.pump", "no host event"]
    assert gaps[0][1] == pytest.approx(22e-6)
    assert gaps[1][1] == pytest.approx(1e-6)


def test_names():
    assert tr.instruction("%tds_conv.18 = f32[8] custom-call()") == \
        "tds_conv.18"
    assert tr.kernel_label("tds_conv.18") == "tds_conv"
    assert tr.kernel_label("custom-call.95") == ""
    assert tr.program("jit_step(4387975846547272356)") == "jit_step"


def test_output_shapes():
    assert tr.output_shape(
        "%tds_conv.1 = f32[8,32,15,80]{3,2,1,0} custom-call(f32[1] %a)") \
        == (8, 32, 15, 80)
    assert tr.output_shape("%hypothesis_unit.3 = (s32[4,128]{1,0}, "
                           "f32[4,128]) custom-call(u32[4,8320] %c)") \
        == (4, 128)
    assert tr.output_shape("tds_conv.1") == ()


def _steps_xspace():
    """Four executions of the step, 10 us each, 20 us apart: a conv call
    (shape in its name, or only in a stat) and two hypothesis-unit calls
    in each."""
    evs, metas = [], []
    mods = []
    for j in range(4):
        t = 20 * j
        mods.append(ev(1, t, 10))
        evs += [ev(2 if j % 2 else 3, t, 2), ev(4, t + 3, 2),
                ev(4, t + 6, 2)]
    metas = [meta(1, "jit_step(7)"),
             meta(2, "%tds_conv.1 = f32[2,16,15,80] custom-call()"),
             meta(4, "%hypothesis_unit.2 = (s32[2,128]) custom-call()")]
    metas.append('event_metadata { key: 3 value { id: 3 name: "tds_conv.1" '
                 '} }')
    # the name-only conv carries its instruction in a stat of its events
    evs = [e.replace("metadata_id: 3 offset_ps",
                     "stats { metadata_id: 9 str_value: \"%tds_conv.1 = "
                     "f32[2,16,15,80] custom-call()\" } "
                     "metadata_id: 3 offset_ps") if "metadata_id: 3 " in e
           else e for e in evs]
    return f"""
planes {{
  id: 1
  name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0 {" ".join(mods)} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0 {" ".join(evs)} }}
  {" ".join(metas)}
  stat_metadata {{ key: 9 value {{ id: 9 name: "long_name" }} }}
}}
"""


def test_whole_executions_with_their_kernel_calls():
    from jax.profiler import ProfileData
    red = tr.reduce_profile(ProfileData.from_text_proto(_steps_xspace()),
                            80e-6)
    exs = red.executions("jit_step")
    # the first and the last execution may be cut by the span: left out
    assert [round(e.start * 1e6) for e in exs] == [20, 40]
    for e in exs:
        assert e.calls["tds_conv"] == [(pytest.approx(2e-6),
                                        (2, 16, 15, 80))]
        assert [s for _d, s in e.calls["hypothesis_unit"]] == [(2, 128)] * 2
    assert red.kernel_shapes()[0] == ["hypothesis_unit", [2, 128], 8]
