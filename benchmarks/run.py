"""Benchmark harness — one function per paper table/figure + system benches.

Prints ``name,us_per_call,derived`` CSV rows (scaffold contract).
``--rows`` selects row groups (``paper``, ``decode``, ``sharded``,
``kernels``, ``dryrun``, or ``all``); ``--json PATH`` additionally writes the
name -> µs mapping as JSON (the CI bench-smoke job uploads
``BENCH_decode.json`` built from the kernel + decode groups; the copy
at the repo root records the perf trajectory, including the
pre-refactor sequential-vs-batched decode rows under ``*_pre_refactor``
keys).

  fig9_layer_sizes    — paper Fig. 9: TDS layer weight sizes (KB)
  fig11_kernel_times  — paper Fig. 11: per-kernel exec time via the
                        instruction-count model (§5.1)
  sec54_realtime      — paper §5.4 headline: decoding-step time vs the
                        80 ms audio window (paper: ~40 ms => 2x real-time)
  rtf_measured        — measured JAX wall-clock RTF of the streaming
                        decoder on this CPU (not the ASRPU estimate)
  beam_throughput     — hypothesis-expansion executions/sec (measured)
  multistream         — sequential vs batched (slot-pool) ASR serving
                        throughput over the same utterances
  sharded (group)     — the model-parallel (--mesh) serving step over 2
                        host devices: acoustic step + batched serve
                        (skipped rows on a 1-device host)
  sharded2d (group)   — the 2D ('data','model') mesh serving step over
                        4 host devices (--mesh 2x2): slot pool sharded
                        on 'data', weights on 'model' (skipped rows
                        below 4 devices)
  kernel_<name>       — Pallas kernels, interpret-mode wall time +
                        analytic v5e roofline time (derived column)
  dryrun_summary      — roofline terms per dry-run artifact (if present)
"""
from __future__ import annotations

import json
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import asrpu_model
from repro.configs.tds_asr import (FEATURE_CONFIG, TDS_CONFIG, DecoderConfig)
from repro.core import decoder, features, lexicon as lx
from repro.kernels import ops
from repro.models import tds

ROWS = []


def row(name: str, us_per_call: float, derived: str = ""):
    ROWS.append((name, us_per_call, derived))
    print(f"{name},{us_per_call:.2f},{derived}", flush=True)


def _timeit(fn, *args, n=5, warmup=2):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / n * 1e6, out


# ---------------------------------------------------------------------------
def fig9_layer_sizes():
    """Layer weight KB; paper: convs in a few KB, FCs in the MB range,
    example 1200x1200 FC = 1.4MB split into 2 kernels of 600 neurons."""
    specs = tds.build_kernel_specs(TDS_CONFIG)
    conv_kb = [s.weight_bytes / 1024 for s in specs if s.kind == "conv"]
    fc_kb = [s.weight_bytes / 1024 for s in specs if s.kind in ("fc", "head")]
    row("fig9_conv_max_kb", 0.0, f"{max(conv_kb):.1f}")
    row("fig9_fc_max_kb", 0.0, f"{max(fc_kb):.1f}")
    fc1200 = [s for s in specs if s.n_in == 1200 and s.kind == "fc"][0]
    row("fig9_fc1200_mb", 0.0,
        f"{fc1200.weight_bytes/2**20:.2f}MB_in_{fc1200.n_subkernels}_kernels")
    assert max(conv_kb) < 64 and max(fc_kb) > 1024  # paper's shape of Fig 9


def fig11_kernel_times():
    """Per-kernel execution time estimates (instruction-count model)."""
    times = asrpu_model.step_breakdown()
    by_kind = {}
    for k in times:
        by_kind.setdefault(k.kind, 0.0)
        by_kind[k.kind] += k.time_ms
    for kind, ms in sorted(by_kind.items()):
        row(f"fig11_{kind}_ms", ms * 1e3, f"{ms:.2f}ms_per_step")
    worst = max(times, key=lambda k: k.time_ms)
    row("fig11_slowest_kernel", worst.time_ms * 1e3, worst.name)


def sec54_realtime():
    est = asrpu_model.step_time_ms()
    rtf = asrpu_model.realtime_factor()
    row("sec54_step_ms_est", est * 1e3,
        f"paper=40ms;model={est:.1f}ms_per_80ms")
    row("sec54_rtf_est", 0.0,
        f"{rtf:.2f}x_realtime(paper=0.50;<1_is_realtime)")


# ---------------------------------------------------------------------------
def rtf_measured():
    """Actual CPU wall-clock of the fused decoding step (full TDS),
    streamed through one serving-engine session in 80 ms pushes."""
    from repro.serving import AsrEngine, AsrProgram, EngineConfig

    words = {f"w{i}": [1 + (i * 7 + j) % 30 for j in range(3)]
             for i in range(20)}
    lex = lx.build_lexicon(words, max_children=32)
    lm = lx.uniform_bigram(len(words))
    params = tds.init_tds(jax.random.PRNGKey(0), TDS_CONFIG)
    program = AsrProgram(TDS_CONFIG, lex, lm,
                         dec_cfg=DecoderConfig(beam_size=64))
    engine = AsrEngine(EngineConfig(program, n_slots=1), params)
    audio = np.random.RandomState(0).randn(16000 * 2).astype(np.float32)
    spp = engine.plan.samples_per_step
    session = engine.open()
    session.push(audio[:spp * 2]).poll()     # warmup/compile
    t0 = time.perf_counter()
    n = 0
    for off in range(spp * 2, len(audio) - spp, spp):
        session.push(audio[off:off + spp]).poll()
        n += 1
    dt = time.perf_counter() - t0
    per_step = dt / max(n, 1)
    row("rtf_measured_step", per_step * 1e6,
        f"cpu_rtf={per_step/0.080:.2f}")


def multistream_throughput():
    """Sequential vs batched ASR serving over the same utterance set: a
    1-slot serving engine decoding utterances back-to-back vs a B-slot
    pool advancing all of them through one vmapped decoding step."""
    from repro.data.pipeline import SyntheticASR
    from repro.launch.serve import asr_demo_engine

    single, words = asr_demo_engine(1)
    data = SyntheticASR(words)
    utts = [data.utterance(i)["audio"] for i in range(4)]
    audio_s = sum(len(a) for a in utts) / 16000

    # warmup must cover the full timed shape set (every (sub-batch,
    # window-bucket) jit entry the schedule hits + finalize + best +
    # slot reset on re-admission), not just the fused step, or one-time
    # tracing/compiles land in the timed region — serving the SAME
    # utterance set replays the exact schedule
    single.serve(utts)
    single.reset()
    t0 = time.perf_counter()
    single.serve(utts)        # 1 slot => utterances decode back-to-back
    dt_seq = time.perf_counter() - t0

    multi, _ = asr_demo_engine(len(utts))
    multi.serve(utts)                             # warmup/compile
    multi.reset()
    t0 = time.perf_counter()
    multi.serve(utts)
    dt_bat = time.perf_counter() - t0

    row("serve_asr_sequential", dt_seq * 1e6,
        f"rtf={dt_seq/audio_s:.3f};{audio_s/dt_seq:.2f}x_realtime")
    row("serve_asr_batched_b4", dt_bat * 1e6,
        f"rtf={dt_bat/audio_s:.3f};{audio_s/dt_bat:.2f}x_realtime;"
        f"speedup={dt_seq/dt_bat:.2f}x")


def sharded_rows():
    """Model-parallel serving on host devices (--mesh): TDS FC/head
    weights split over a 2-wide ('model',) mesh, the fused step under
    shard_map.  Needs >= 2 jax devices — the CI bench-smoke job runs
    this group in a second process with
    XLA_FLAGS=--xla_force_host_platform_device_count=2 (the flag must
    precede jax init); on a 1-device host the rows are emitted as
    skipped.  NOTE on CPU hosts the 'devices' share the same cores, so
    these rows track the sharded path's health/overhead trajectory —
    the weight-bandwidth win needs real accelerator devices."""
    if jax.device_count() < 2:
        # NOT recorded as rows: a 0.0 "measurement" merged into the JSON
        # would shadow the committed baseline and silently pass
        # compare.py; an absent row triggers its missing-row ::warning::
        print("# sharded rows skipped: needs >= 2 devices "
              "(XLA_FLAGS=--xla_force_host_platform_device_count=2)",
              flush=True)
        return
    from jax.sharding import PartitionSpec as P

    from repro.data.pipeline import SyntheticASR
    from repro.launch.serve import asr_demo_engine, serve_mesh
    from repro.parallel import sharding as shlib

    mesh = shlib.make_mesh((2,), ("model",))
    params = tds.init_tds(jax.random.PRNGKey(0), TDS_CONFIG)
    fc = FEATURE_CONFIG
    nfr = 8
    need = fc.frame_len + (nfr - 1) * fc.frame_shift
    pspecs = shlib.tds_param_specs(TDS_CONFIG, mesh)
    placed = shlib.place_tree(params, pspecs, mesh)

    def body(p, ss, x):
        feats = features.mfcc(x, fc, use_pallas=True, hot=True)[:, :nfr]
        return tds.forward_batched(p, TDS_CONFIG, feats, ss, axis="model")

    step = jax.jit(jax.shard_map(body, mesh=mesh,
                                 in_specs=(pspecs, P(), P()),
                                 out_specs=(P(), P()), check_vma=False))
    R = np.random.RandomState(0)
    ss = tds.init_batched_stream_state(TDS_CONFIG, 4)
    x = jnp.asarray(R.randn(4, need).astype(np.float32))
    us, _ = _timeit(step, placed, ss, x, n=5, warmup=2)
    row("acoustic_step_sharded", us,
        f"d2_model_parallel_b4;{us/4:.0f}us_per_slot")

    engine, words = asr_demo_engine(4, mesh=serve_mesh(2))
    data = SyntheticASR(words)
    utts = [data.utterance(i)["audio"] for i in range(4)]
    audio_s = sum(len(a) for a in utts) / 16000
    engine.serve(utts)        # warmup replays the exact timed schedule
    engine.reset()
    t0 = time.perf_counter()
    engine.serve(utts)
    dt = time.perf_counter() - t0
    row("serve_asr_sharded_d2", dt * 1e6,
        f"rtf={dt/audio_s:.3f};{audio_s/dt:.2f}x_realtime;model_parallel=2")


def sharded_2d_rows():
    """2D ('data','model') mesh serving on host devices (--mesh RxC):
    the slot pool shards over a 2-wide 'data' axis (each shard holds
    b/2 slots end-to-end) while FC/head weights shard over a 2-wide
    'model' axis.  Needs >= 4 jax devices — the CI bench-smoke job runs
    this group in its own process with
    XLA_FLAGS=--xla_force_host_platform_device_count=4; on a smaller
    host the rows are emitted as skipped (see sharded_rows).  Same CPU
    caveat: forced host devices share cores, so these rows pin the 2D
    path's health/overhead — the throughput-scaling win needs real
    accelerator devices (ROADMAP item 5)."""
    if jax.device_count() < 4:
        print("# sharded2d rows skipped: needs >= 4 devices "
              "(XLA_FLAGS=--xla_force_host_platform_device_count=4)",
              flush=True)
        return
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.data.pipeline import SyntheticASR
    from repro.launch.serve import asr_demo_engine, serve_mesh
    from repro.parallel import sharding as shlib

    mesh = shlib.make_mesh((2, 2), ("data", "model"))
    params = tds.init_tds(jax.random.PRNGKey(0), TDS_CONFIG)
    fc = FEATURE_CONFIG
    nfr = 8
    need = fc.frame_len + (nfr - 1) * fc.frame_shift
    pspecs = shlib.tds_param_specs(TDS_CONFIG, mesh)
    placed = shlib.place_tree(params, pspecs, mesh)

    def body(p, ss, x):
        feats = features.mfcc(x, fc, use_pallas=True, hot=True)[:, :nfr]
        return tds.forward_batched(p, TDS_CONFIG, feats, ss, axis="model")

    ss = tds.init_batched_stream_state(TDS_CONFIG, 4)
    sspecs = shlib.asr_state_specs(ss, mesh)
    step = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(pspecs, sspecs, P("data", None)),
        out_specs=(P("data", None, None), sspecs), check_vma=False))
    R = np.random.RandomState(0)
    ss = shlib.place_tree(ss, sspecs, mesh)
    x = jax.device_put(R.randn(4, need).astype(np.float32),
                       NamedSharding(mesh, P("data", None)))
    us, _ = _timeit(step, placed, ss, x, n=5, warmup=2)
    row("acoustic_step_2d", us,
        f"2x2_data_x_model_b4;{us/4:.0f}us_per_slot")

    engine, words = asr_demo_engine(4, mesh=serve_mesh("2x2"))
    data = SyntheticASR(words)
    utts = [data.utterance(i)["audio"] for i in range(4)]
    audio_s = sum(len(a) for a in utts) / 16000
    engine.serve(utts)        # warmup replays the exact timed schedule
    engine.reset()
    t0 = time.perf_counter()
    engine.serve(utts)
    dt = time.perf_counter() - t0
    row("serve_asr_2d_d4", dt * 1e6,
        f"rtf={dt/audio_s:.3f};{audio_s/dt:.2f}x_realtime;mesh=2x2")


def acoustic_steps():
    """The acoustic half of the decoding step — fused-logmel MFCC tail +
    the slot-native TDS forward — jitted, at B=1 and B=4 slots (the
    (B, T) rows fold into one matmul row dimension per kernel)."""
    params = tds.init_tds(jax.random.PRNGKey(0), TDS_CONFIG)
    fc = FEATURE_CONFIG
    nfr = 8
    need = fc.frame_len + (nfr - 1) * fc.frame_shift

    @jax.jit
    def step(p, ss, x):
        feats = features.mfcc(x, fc, use_pallas=True, hot=True)[:, :nfr]
        return tds.forward_batched(p, TDS_CONFIG, feats, ss)

    R = np.random.RandomState(0)
    for b in (1, 4):
        ss = tds.init_batched_stream_state(TDS_CONFIG, b)
        x = jnp.asarray(R.randn(b, need).astype(np.float32))
        us, _ = _timeit(step, params, ss, x, n=5, warmup=2)
        row(f"acoustic_step_b{b}", us,
            f"fused_mfcc+tds_forward;{us/b:.0f}us_per_slot")


def beam_throughput():
    words = {f"w{i}": [1 + (i * 7 + j) % 30 for j in range(3)]
             for i in range(20)}
    lex = lx.build_lexicon(words, max_children=32)
    lm = lx.uniform_bigram(len(words))
    cfg = DecoderConfig(beam_size=128)
    logp = jax.nn.log_softmax(
        jnp.asarray(np.random.RandomState(0).randn(32).astype(np.float32)))
    st = decoder.init_state(cfg.beam_size, lm)
    step = jax.jit(lambda s, lp: decoder.expand_step(s, lp, lex, lm, cfg))
    us, _ = _timeit(step, st, logp, n=20)
    row("beam_expand_step", us, f"{1e6/us:.0f}_expansions_per_s")


# ---------------------------------------------------------------------------
V5E_FLOPS = 197e12
V5E_HBM = 819e9


def kernel_benches():
    R = np.random.RandomState(0)
    # int8 matmul — ASRPU's hot loop: the 1200x1200 FC layer (fig 9)
    x = jnp.asarray(R.randn(8, 1200).astype(np.float32))
    w = jnp.asarray(R.randn(1200, 1200).astype(np.float32))
    us, _ = _timeit(ops.int8_matmul, x, w, n=3, warmup=1)
    flops = 2 * 8 * 1200 * 1200
    v5e_us = max(flops / (V5E_FLOPS * 2),          # int8 ~2x bf16 peak
                 (1200 * 1200 + 8 * 1200 * 2) / V5E_HBM) * 1e6
    row("kernel_int8_matmul_fc1200", us, f"v5e_est={v5e_us:.2f}us")

    q = jnp.asarray(R.randn(1, 8, 256, 64).astype(np.float32))
    us, _ = _timeit(lambda: ops.flash_attention(q, q, q, block_q=64,
                                                block_kv=64), n=3, warmup=1)
    flops = 2 * 2 * 8 * 256 * 256 * 64 * 0.5
    row("kernel_flash_attention_256", us,
        f"v5e_est={flops/V5E_FLOPS*1e6:.2f}us")

    xx = jnp.asarray(R.randn(512, 1840).astype(np.float32))
    s = jnp.ones((1840,), jnp.float32)
    b = jnp.zeros((1840,), jnp.float32)
    us, _ = _timeit(ops.layernorm, xx, s, b, n=3, warmup=1)
    bytes_ = 2 * 512 * 1840 * 4
    row("kernel_layernorm_512x1840", us,
        f"v5e_est={bytes_/V5E_HBM*1e6:.2f}us")

    p = jnp.abs(jnp.asarray(R.randn(256, 257).astype(np.float32)))
    fb = jnp.asarray(features.mel_filterbank(FEATURE_CONFIG))
    dct = jnp.asarray(features.dct_matrix(80, 80))
    us, _ = _timeit(ops.logmel, p, fb, dct, n=3, warmup=1)
    row("kernel_logmel_256", us, "fused_mel+log+dct")

    sc = jnp.asarray(R.randn(8448).astype(np.float32))
    us, _ = _timeit(lambda: ops.beam_prune(sc, 25.0), n=3, warmup=1)
    row("kernel_beam_prune_8448", us, "hypothesis_unit_threshold")

    # fused hypothesis unit: merge + threshold + top-k in one op over a
    # beam-128 / 32-children candidate set (N = 128 * 65), batch of 4
    # slots — the decode hot path's shape
    hh = jnp.asarray(R.randint(0, 4096, (4, 8320)).astype(np.int32))
    hp = jnp.asarray((R.randn(4, 8320) * 3).astype(np.float32))
    hq = jnp.asarray((R.randn(4, 8320) * 3).astype(np.float32))
    ref_policy = ops.KernelPolicy("ref")
    us, _ = _timeit(lambda: ops.hypothesis_unit(hh, hp, hq, 128, 25.0,
                                                policy=ref_policy),
                    n=3, warmup=1)
    row("kernel_hypothesis_unit_b4_n8320", us, "fused_merge+threshold+topk")

    xc = jnp.asarray(R.randn(8 + 64, 80, 15).astype(np.float32))
    wc = jnp.asarray(R.randn(9, 15, 15).astype(np.float32) * 0.1)
    bc = jnp.zeros((15,), jnp.float32)
    us, _ = _timeit(lambda: ops.tds_conv(xc, wc, bc), n=3, warmup=1)
    row("kernel_tds_conv_64", us, "stage1_conv")

    # the full 79-kernel TDS sequence, one 80 ms window (the acoustic
    # model inside every decoding step)
    tparams = tds.init_tds(jax.random.PRNGKey(0), TDS_CONFIG)
    feats8 = jnp.asarray(R.randn(8, 80).astype(np.float32))
    fwd = jax.jit(lambda p, f: tds.forward(p, TDS_CONFIG, f)[0])
    us, _ = _timeit(fwd, tparams, feats8, n=3, warmup=1)
    row("kernel_tds_forward", us, "79_kernel_sequence_T8")


def dryrun_summary():
    art = pathlib.Path(__file__).resolve().parents[1] / "artifacts" / "dryrun"
    if not art.exists():
        row("dryrun_summary", 0.0, "no_artifacts")
        return
    n_ok = n_skip = n_fail = 0
    worst = (0.0, "")
    for f in sorted(art.glob("*.json")):
        rec = json.loads(f.read_text())
        if rec["status"] == "ok":
            n_ok += 1
            r = rec.get("roofline", {})
            t = max(r.get("t_compute", 0), r.get("t_memory", 0),
                    r.get("t_collective", 0))
            if t > worst[0]:
                worst = (t, f.stem)
        elif rec["status"] == "skipped":
            n_skip += 1
        else:
            n_fail += 1
    row("dryrun_cells", 0.0, f"ok={n_ok};skipped={n_skip};fail={n_fail}")
    row("dryrun_worst_cell", worst[0] * 1e6, worst[1])


GROUPS = {
    "paper": (fig9_layer_sizes, fig11_kernel_times, sec54_realtime),
    "decode": (beam_throughput, acoustic_steps, multistream_throughput,
               rtf_measured),
    "sharded": (sharded_rows,),
    "sharded2d": (sharded_2d_rows,),
    "kernels": (kernel_benches,),
    "dryrun": (dryrun_summary,),
}
GROUP_ORDER = ("paper", "decode", "sharded", "sharded2d", "kernels",
               "dryrun")


def main(argv=None) -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--rows", default="all",
                    help="comma-separated row groups to run: "
                         f"{', '.join(GROUP_ORDER)} or all")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the name -> us_per_call mapping as "
                         "JSON (e.g. BENCH_decode.json at the repo root)")
    args = ap.parse_args(argv)
    from repro.runtime import compile_cache
    compile_cache.use_persistent_cache()

    wanted = [g.strip() for g in args.rows.split(",") if g.strip()]
    if "all" in wanted:
        wanted = list(GROUP_ORDER)
    unknown = set(wanted) - set(GROUPS)
    if unknown:
        ap.error(f"unknown row group(s): {sorted(unknown)}")

    print("name,us_per_call,derived")
    for group in GROUP_ORDER:
        if group in wanted:
            for fn in GROUPS[group]:
                fn()

    if args.json:
        path = pathlib.Path(args.json)
        # merge-update: rows not re-measured this run (other groups,
        # recorded *_pre_refactor trajectory keys) are preserved
        payload = {}
        if path.exists():
            payload = json.loads(path.read_text())
        payload.update({name: round(us, 2) for name, us, _ in ROWS})
        path.write_text(json.dumps(payload, indent=2) + "\n")
        print(f"wrote {len(ROWS)} rows to {path} "
              f"({len(payload)} total)", flush=True)


if __name__ == "__main__":
    main()
