"""Serving launcher — both modes run on the unified serving engine.

Two modes, one shape (repro.serving): a fixed slot pool owned by an
`Engine`, advanced by one fused (vmapped) step, with per-connection
`Session` handles streaming input in and output out:

  * --mode lm  : batched LM serving for any --arch (tiny configs on CPU):
                 an `LmEngine` slot pool with PER-SLOT cache positions,
                 so staggered admissions with unequal prompt lengths
                 decode correctly; each serve step is one fused
                 decode_step over all slots.
  * --mode asr : the paper's system as an `AsrEngine` — sessions stream
                 80 ms audio chunks via Session.push/poll/finish; with
                 --streams N > 1 the N-slot pool decodes N concurrent
                 utterances through one vmapped decoding step
                 (continuous batching, like --mode lm).

  PYTHONPATH=src python -m repro.launch.serve --mode asr --utterances 3
  PYTHONPATH=src python -m repro.launch.serve --mode asr --streams 4
  PYTHONPATH=src python -m repro.launch.serve --mode lm --arch mamba2-1.3b \
      --requests 8 --max-new 32
  XLA_FLAGS=--xla_force_host_platform_device_count=2 PYTHONPATH=src \
      python -m repro.launch.serve --mode asr --streams 4 --mesh 2
  XLA_FLAGS=--xla_force_host_platform_device_count=4 PYTHONPATH=src \
      python -m repro.launch.serve --mode asr --streams 4 --mesh 2x2

`--mesh N` runs the ASR fused step model-parallel: every TDS FC/head
weight is sharded over N devices on its feature axis and the step runs
under shard_map (partial-sum + all-reduce per matmul) — each device
reads 1/N of the FC weight bytes per window, the lever the flat B=1
`rtf_measured_step` is bound by (see ROADMAP).  `--mesh RxC` makes the
mesh 2D ('data', 'model'): the slot pool shards over the R-way 'data'
axis (each data shard decodes n_slots/R slots end-to-end — beam
expansion is slot-parallel, so no 'data' collectives) while weights
shard over the C-way 'model' axis, the layout that scales serve
throughput with device count.  `--overlap-psum` chunks the model-axis
all-reduces so they hide under the next chunk's matmul.  Transcripts
are parity-tested against the unsharded engine
(tests/test_sharded_serving).
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.kernels.policy import KernelPolicy
from repro.launch.steps import build_lm
from repro.parallel.sharding import make_mesh
from repro.serving import (AsrEngine, AsrProgram, EngineConfig, LmEngine,
                           LmProgram)


def _policy(args) -> KernelPolicy:
    return KernelPolicy(args.kernels)


def serve_mesh(spec):
    """`--mesh` spec -> a serving Mesh, or None for the exact unsharded
    single-device step.

      * N (int or "N")  : 1-axis ('model',) mesh over N devices — PR 5's
                          feature-axis weight sharding; N <= 1 -> None.
      * "RxC"           : 2-axis ('data', 'model') mesh over R*C devices
                          — the slot pool shards over the R-way 'data'
                          axis, FC/head weights over the C-way 'model'
                          axis.  "1x1" -> a real 1x1 mesh (exercises the
                          2D step path on one device).

    On a CPU host the devices come from
    XLA_FLAGS=--xla_force_host_platform_device_count (set it BEFORE the
    process starts; jax locks the device count at first use)."""
    def _need(n, what):
        if jax.device_count() < n:
            raise SystemExit(
                f"--mesh {what} needs {n} devices but jax sees "
                f"{jax.device_count()}; on a CPU host prefix the command "
                f"with XLA_FLAGS=--xla_force_host_platform_device_count"
                f"={n}")

    if isinstance(spec, str) and "x" in spec:
        try:
            r, c = (int(v) for v in spec.split("x"))
        except ValueError:
            raise SystemExit(f"--mesh {spec!r}: expected N or RxC")
        if r < 1 or c < 1:
            raise SystemExit(f"--mesh {spec!r}: axes must be >= 1")
        _need(r * c, spec)
        return make_mesh((r, c), ("data", "model"))
    n_model = int(spec)
    if n_model <= 1:
        return None
    _need(n_model, n_model)
    return make_mesh((n_model,), ("model",))


def serve_lm(args):
    cfg = get_config(args.arch).tiny()
    lm = build_lm(cfg, None)
    params = lm.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    # vary prompt lengths so bucketed admission is exercised (one
    # masked multi-row prefill jit entry per bucket, not per length)
    plens = [max(1, args.prompt_len - (i % 4)) for i in range(args.requests)]
    prompts = [rng.integers(1, cfg.vocab_size, n) for n in plens]

    program = LmProgram(cfg, cache_len=args.prompt_len + args.max_new,
                        max_new=args.max_new)
    engine = LmEngine(EngineConfig(program, n_slots=args.slots,
                                   kernels=_policy(args)), params)

    t0 = time.time()
    outputs = engine.serve(prompts)
    dt = time.time() - t0
    total_tokens = sum(len(v) for v in outputs)
    print(f"served {len(outputs)} requests, {total_tokens} tokens, "
          f"{engine.n_steps} decode steps in {dt:.2f}s "
          f"({total_tokens/dt:.1f} tok/s); "
          f"{engine.prefill_cache_entries()} prefill jit entries over "
          f"buckets {program.buckets()}")
    return dict(enumerate(outputs))


def asr_demo_system():
    """Small-TDS ASR system shared by the asr serving paths and
    benchmarks/run.py (public: external harnesses build on it)."""
    from repro.configs.tds_asr import DECODER_CONFIG, TDSConfig, TDSStage
    from repro.core import lexicon as lx
    from repro.models import tds

    # small TDS so it runs fast on CPU; same kernel structure
    tds_cfg = TDSConfig(
        stages=(TDSStage(1, 4, 80, 9, 2), TDSStage(1, 4, 80, 9, 2),
                TDSStage(1, 6, 80, 9, 2)),
        vocab_size=32)
    words = {f"w{i}": [1 + (i * 3 + j) % 30 for j in range(2 + i % 3)]
             for i in range(12)}
    lex = lx.build_lexicon(words, max_children=16)
    lm = lx.uniform_bigram(len(words))
    params = tds.init_tds(jax.random.PRNGKey(0), tds_cfg)
    return tds_cfg, words, lex, lm, params, DECODER_CONFIG


PAPER_LEXICON_WORDS = 4000     # a few thousand words, as in the paper's task


def paper_asr_system(seed: int = 0):
    """The paper's system at its published widths: `TDS_CONFIG` (79
    kernels, widths 1200/1520/1840, 9000 outputs) under
    `DECODER_CONFIG` (beam 128, trie fanout 32), with a lexicon of
    `PAPER_LEXICON_WORDS` words and a bigram LM.  Weights, lexicon and
    LM are random, generated from `seed`; nothing is read from disk.
    Returns the same tuple as `asr_demo_system`."""
    from repro.configs.tds_asr import DECODER_CONFIG, TDS_CONFIG
    from repro.core import lexicon as lx
    from repro.models import tds

    n_words = PAPER_LEXICON_WORDS
    rng = np.random.default_rng(seed)
    fanout, vocab = DECODER_CONFIG.max_children, TDS_CONFIG.vocab_size
    # grow the trie word by word: at each node reuse a child or, while
    # the node has room, branch on a fresh token — fanout stays <= C
    kids = [{}]
    is_word = [False]
    words = {}
    while len(words) < n_words:
        node, toks = 0, []
        for _ in range(int(rng.integers(2, 7))):
            if kids[node] and (len(kids[node]) >= fanout
                               or rng.random() < 0.7):
                tok = list(kids[node])[int(rng.integers(len(kids[node])))]
            else:
                tok = int(rng.integers(1, vocab))
                while tok in kids[node]:
                    tok = int(rng.integers(1, vocab))
                kids[node][tok] = len(kids)
                kids.append({})
                is_word.append(False)
            node = kids[node][tok]
            toks.append(tok)
        if not is_word[node]:
            is_word[node] = True
            words[f"w{len(words)}"] = toks
    lex = lx.build_lexicon(words, max_children=fanout)
    zipf = 1.0 / np.arange(1, n_words + 1)
    counts = rng.poisson(n_words * zipf / zipf.sum() * 8.0,
                         size=(n_words + 1, n_words))
    lm = lx.bigram_from_counts(counts)
    params = tds.init_tds(jax.random.PRNGKey(seed), TDS_CONFIG)
    return TDS_CONFIG, words, lex, lm, params, DECODER_CONFIG


def asr_demo_engine(n_slots: int, kernels: KernelPolicy = None,
                    mesh=None, max_queue=None,
                    overlap_psum: bool = False,
                    session_deadline=None, worker_watchdog=None,
                    faults=None, system=None) -> tuple:
    """(engine, words): an AsrEngine over `system` — the tuple
    `asr_demo_system` or `paper_asr_system` returns (default: the demo
    system).  `mesh` (see `serve_mesh`) shards the TDS FC/head weights
    over its 'model' axis — and, with a 'data' axis, the slot pool —
    running the fused step under shard_map; `overlap_psum` enables the
    latency-hiding psum split on the sharded contractions; `max_queue`
    is the admission backpressure bound (`EngineConfig.max_queue`);
    `session_deadline`/`worker_watchdog`/`faults` are the
    fault-tolerance knobs (see README "Fault tolerance")."""
    tds_cfg, words, lex, lm, params, dec_cfg = system or asr_demo_system()
    program = AsrProgram(tds_cfg, lex, lm, dec_cfg=dec_cfg,
                        ).with_beam_width(25.0)
    engine = AsrEngine(EngineConfig(program, n_slots=n_slots,
                                    kernels=kernels or KernelPolicy(),
                                    mesh=mesh, max_queue=max_queue,
                                    overlap_psum=overlap_psum,
                                    session_deadline=session_deadline,
                                    worker_watchdog=worker_watchdog,
                                    faults=faults),
                       params)
    return engine, words


def serve_asr(args):
    """Single-stream streaming ASR: one Session per utterance, pushing
    80 ms chunks; poll() tracks the live best hypothesis."""
    from repro.data.pipeline import SyntheticASR

    engine, words = asr_demo_engine(1, _policy(args), serve_mesh(args.mesh),
                                    overlap_psum=args.overlap_psum)
    data = SyntheticASR(words)
    spp = engine.plan.samples_per_step
    n_utts = 2 if args.utterances is None else args.utterances
    for u in range(n_utts):
        utt = data.utterance(u)
        t0 = time.time()
        audio = utt["audio"]
        session = engine.open()
        # stream in 80ms chunks — one push per chunk, poll for live best
        for off in range(0, len(audio), spp):
            session.push(audio[off:off + spp])
            session.poll()
        best = session.finish()
        dt = time.time() - t0
        rtf = dt / (len(audio) / 16000)
        print(f"utt {u}: {len(audio)/16000:.2f}s audio, decoded in {dt:.2f}s "
              f"(RTF {rtf:.2f}), steps={best['steps']}, "
              f"best words={best['words'].tolist()} score={best['score']:.2f} "
              f"(ref={utt['words'].tolist()})")


def serve_asr_multistream(args):
    """Multi-stream ASR serving: a B-slot pool of concurrent utterance
    streams, one vmapped/jitted decoding step advancing all active slots
    (continuous batching, mirroring serve_lm's slot pool)."""
    from repro.data.pipeline import SyntheticASR

    engine, words = asr_demo_engine(args.streams, _policy(args),
                                    serve_mesh(args.mesh),
                                    overlap_psum=args.overlap_psum)
    data = SyntheticASR(words)
    # default: one utterance per slot; an explicit --utterances wins
    # (fewer than --streams just leaves the extra slots masked idle)
    n_utts = args.utterances if args.utterances is not None \
        else max(args.streams, 2)
    utts = [data.utterance(u) for u in range(n_utts)]
    audio_s = sum(len(u["audio"]) for u in utts) / 16000
    t0 = time.time()
    results = engine.serve([u["audio"] for u in utts])
    dt = time.time() - t0
    for u, (utt, best) in enumerate(zip(utts, results)):
        print(f"utt {u}: {len(utt['audio'])/16000:.2f}s audio, "
              f"steps={best['steps']}, best words={best['words'].tolist()} "
              f"score={best['score']:.2f} (ref={utt['words'].tolist()})")
    print(f"served {n_utts} utterances ({audio_s:.2f}s audio) over "
          f"{args.streams} streams in {dt:.2f}s: "
          f"{engine.n_steps} vmapped decoding steps, "
          f"RTF {dt/audio_s:.2f}, throughput {audio_s/dt:.2f}x realtime")
    return results


def serve_network(args):
    """`--serve`: bind the asyncio network front-end over the demo
    engines (ASR always; plus a tiny LM engine) and serve until
    interrupted.  Each engine's step loop runs on its own EngineWorker
    thread, so sessions stream over HTTP chunked transfer while the
    fused steps batch across them (see repro.serving.server).

    SIGTERM/SIGINT trigger a graceful drain: the listener stops
    accepting, in-flight sessions run to their final result
    (bounded by --drain-timeout), then the workers stop — the contract
    a rolling restart behind a load balancer needs."""
    import asyncio
    import signal

    from repro.serving.server import EngineServer

    asr_engine, _ = asr_demo_engine(args.streams, _policy(args),
                                    serve_mesh(args.mesh),
                                    max_queue=args.max_queue,
                                    overlap_psum=args.overlap_psum,
                                    session_deadline=args.session_deadline,
                                    worker_watchdog=args.watchdog)
    lm_cfg = get_config(args.arch).tiny()
    lm = build_lm(lm_cfg, None)
    lm_program = LmProgram(lm_cfg, cache_len=args.prompt_len + args.max_new,
                           max_new=args.max_new)
    lm_engine = LmEngine(
        EngineConfig(lm_program, n_slots=args.slots, kernels=_policy(args),
                     max_queue=args.max_queue,
                     session_deadline=args.session_deadline,
                     worker_watchdog=args.watchdog),
        lm.init(jax.random.PRNGKey(0)))

    async def run():
        server = EngineServer(asr_engine=asr_engine, lm_engine=lm_engine,
                              host=args.host, port=args.port,
                              asr_idle_timeout=args.idle_timeout)
        await server.start()
        stop = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                loop.add_signal_handler(sig, stop.set)
            except (NotImplementedError, RuntimeError):
                pass             # platform without loop signal handlers
        print(f"serving ASR ({args.streams} slots) + LM ({args.slots} "
              f"slots) on http://{server.host}:{server.port} "
              f"(max_queue={args.max_queue}, watchdog={args.watchdog}, "
              f"session_deadline={args.session_deadline}); POST /asr, "
              f"POST /lm, GET /metrics, GET /healthz")
        try:
            serve = asyncio.ensure_future(server.serve_forever())
            stopper = asyncio.ensure_future(stop.wait())
            await asyncio.wait({serve, stopper},
                               return_when=asyncio.FIRST_COMPLETED)
            serve.cancel()
            stopper.cancel()
            if stop.is_set():
                print("signal received: draining in-flight sessions ...")
        finally:
            await server.aclose(drain=True, timeout=args.drain_timeout)
            print("drained; server stopped")

    try:
        asyncio.run(run())
    except KeyboardInterrupt:
        pass


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="asr", choices=["lm", "asr"])
    ap.add_argument("--arch", default="mamba2-1.3b")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--utterances", type=int, default=None,
                    help="ASR utterance count (default: 2, or one per "
                         "slot when --streams > 1)")
    ap.add_argument("--streams", type=int, default=1,
                    help="ASR slot-pool size; >1 uses the batched "
                         "multi-stream scheduler")
    ap.add_argument("--kernels", default="auto",
                    choices=["auto", "ref", "interpret", "mosaic"],
                    help="KernelPolicy mode for Pallas-backed decode ops "
                         "(auto: Mosaic on TPU, ref for the hot path on "
                         "CPU)")
    ap.add_argument("--mesh", type=str, default="1", metavar="N|RxC",
                    help="ASR parallel spec: N shards every TDS FC/head "
                         "weight over N devices ('model' mesh axis) and "
                         "runs the fused step under shard_map; RxC "
                         "additionally shards the slot pool over an "
                         "R-way 'data' axis (C-way 'model'), so "
                         "throughput scales with R; 1 = the unsharded "
                         "single-device step (on CPU hosts set "
                         "XLA_FLAGS="
                         "--xla_force_host_platform_device_count=R*C "
                         "first)")
    ap.add_argument("--overlap-psum", action="store_true",
                    help="sharded ASR step: chunk each model-axis "
                         "all-reduce so it overlaps the next chunk's "
                         "local matmul (async-collective backends; "
                         "numerical ~1e-6 parity with the default "
                         "synchronous psum)")
    ap.add_argument("--serve", action="store_true",
                    help="run the asyncio network front-end (HTTP "
                         "chunked streaming over the demo ASR + LM "
                         "engines) instead of the in-process demos")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8300,
                    help="--serve listen port (0 picks a free port)")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="admission backpressure bound: with every slot "
                         "busy and this many sessions queued, new "
                         "sessions get HTTP 503 (default: unbounded)")
    ap.add_argument("--watchdog", type=float, default=None,
                    help="--serve: seconds an engine worker's heartbeat "
                         "may age before the supervisor declares it "
                         "wedged and restarts it (default: only DEAD "
                         "threads restart)")
    ap.add_argument("--session-deadline", type=float, default=None,
                    help="--serve: seconds a session may live from "
                         "open() before the pump reaps it "
                         "(DeadlineExceeded; default: no deadline)")
    ap.add_argument("--idle-timeout", type=float, default=None,
                    help="--serve: seconds /asr waits for the next "
                         "command chunk before freeing a silent "
                         "client's slot (default: wait forever)")
    ap.add_argument("--drain-timeout", type=float, default=30.0,
                    help="--serve: bound on the SIGTERM graceful drain "
                         "(seconds; in-flight sessions finishing)")
    args = ap.parse_args(argv)
    from repro.runtime import compile_cache
    compile_cache.use_persistent_cache()
    if args.serve:
        return serve_network(args)
    if args.mode == "lm":
        if args.mesh not in ("1", "0"):
            ap.error("--mesh is ASR-only (LmEngine rejects a mesh; "
                     "sharded LM serving goes through launch/steps.py "
                     "build_cell)")
        return serve_lm(args)
    if args.streams > 1:
        return serve_asr_multistream(args)
    return serve_asr(args)


if __name__ == "__main__":
    main()
