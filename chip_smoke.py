"""Bring-up smoke run: the streaming ASR server at the paper's widths on TPU.

    python chip_smoke.py              # one chip: the server phase
    python chip_smoke.py --chips 4    # a 2x2 ('data','model') mesh vs one chip

One chip.  The paper's system — `TDS_CONFIG` (79 kernels, widths
1200/1520/1840, 9000 outputs) under `DECODER_CONFIG` (beam 128, trie
fanout 32), a 4000-word lexicon and a bigram LM, all random from
`--seed` (`launch.serve.paper_asr_system`) — is built into an
`AsrEngine` by `launch.serve.asr_demo_engine`, served by an in-process
`EngineServer`, and streamed to by concurrent `AsrClient`s that push
80 ms chunks at real time, poll after each, and finish.  The run passes
only if:

  * the device is a TPU (anything else exits 2, with no result line);
  * the compiled step holds a Mosaic kernel (`tpu_custom_call`) for each
    of logmel, tds_conv, layernorm and hypothesis_unit;
  * every stream got its final transcript with no error chunk, /metrics
    counts no faulted session and the engine's fault log is empty;
  * the served TDS log-probs of one utterance match a plain float32 jnp
    reference run under `jax.default_matmul_precision("highest")`;
  * the hypothesis unit on the chip selects what its jnp twin selects;
  * each served transcript equals a single-stream in-process decode of
    the same audio;
  * side-by-side in-process decodes (slot bucket 8) equal single-stream
    ones (bucket 1) at default precision, and under "highest" their
    tokens match too and their scores agree to float32 noise — the
    witness that the gap allowed at default precision is matmul rounding.

Four chips (`--chips 4`): the same system on a 2x2 mesh and unsharded on
one chip, streaming the same audio in-process; transcripts must be equal
and scores within the psum tolerance the README states.

Earlier lines are observations (compile seconds, latencies, memory); the
last line is `{"ok": true, "device": {...}}`, printed only when every
check passed.  Everything runs in this one process.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import re
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

STREAMS = 6           # concurrent client streams (one chip)
SLOTS = 8             # engine slot pool
WORDS_PER_UTT = 6     # ~3 s of synthetic audio per stream
MESH_STREAMS = 4      # streams of the four-chip comparison
KERNELS = ("logmel", "tds_conv", "layernorm", "hypothesis_unit")

# Served log-probs vs the f32 reference.  On a TPU, matmuls of float32
# operands outside Pallas run as one bfloat16 MXU pass (XLA's DEFAULT
# precision); rounding just the weights and input of the paper-width TDS
# to bfloat16 moves its log-probs by up to 0.057 (measured on CPU), and
# the activations' rounding adds about as much again.
LOGP_TOL_SERVED = 0.25
# The same served path traced under "highest" precision: both sides are
# float32 end to end and differ only in summation order and in the
# exp/log implementations (~1e-6 relative each), through 79 kernels.
LOGP_TOL_F32 = 1e-3
# Served vs single-stream decodes (and sharded vs unsharded) run the
# same program at different slot buckets (or over the 'model' psum), and
# at DEFAULT precision the TPU does not round every bucket's matmuls
# alike: on one v5e chip the scores of 3-s streams moved by up to 3.5e-4
# of their magnitude between buckets 8 and 1, about what bf16 rounding
# does to ~40 frames of log-probs.  A decoding fault moves a score by far
# more: one dropped 80 ms window costs ~6 nats of a ~-250 score.  Word
# sequences must match.  Token sequences are only reported: under random
# weights the top hypotheses are near-ties, and a rounding difference
# that swaps two of them moves the score by less than this tolerance.
SCORE_RTOL = 1e-3
# The same bucket 8 vs bucket 1 comparison traced under "highest": both
# sides are float32 end to end.  The served TDS then matches an
# independent f32 forward to ~1e-5 per log-prob (LOGP_TOL_F32's reading
# on the chip), so ~40 frames move a ~250-nat score by at most ~4e-4
# nats, under 2e-6 of it.  Tokens must match as well.
SCORE_RTOL_F32 = 1e-5


def devices():
    import jax
    return jax.devices()


def build_system(seed: int):
    from repro.launch.serve import paper_asr_system
    return paper_asr_system(seed)


def build_engine(n_slots: int, system, mesh=None):
    from repro.launch.serve import asr_demo_engine
    return asr_demo_engine(n_slots, mesh=mesh, system=system)[0]


def log(msg: str) -> None:
    print(msg, flush=True)


def score_gap(a: dict, b: dict) -> float:
    """|a - b| score, relative to b's magnitude (at least 1)."""
    return (abs(float(a["score"]) - float(b["score"]))
            / max(1.0, abs(float(b["score"]))))


def same_transcript(a: dict, b: dict, rtol: float = SCORE_RTOL,
                    tokens: bool = False) -> bool:
    """Equal word sequences (and token sequences, if asked), scores
    within `rtol`."""
    return (a["words"].tolist() == b["words"].tolist()
            and (not tokens or a["tokens"].tolist() == b["tokens"].tolist())
            and score_gap(a, b) <= rtol)


def compare_line(i: int, names, a: dict, b: dict) -> str:
    tokens = a["tokens"].tolist() == b["tokens"].tolist()
    return (f"stream {i}: {names[0]} {a['words'].tolist()} {a['score']} | "
            f"{names[1]} {b['words'].tolist()} {b['score']}; tokens "
            f"{'equal' if tokens else 'differ'}")


def pallas_kernels(hlo_text: str) -> set:
    """Names of the Pallas kernels compiled to Mosaic custom calls."""
    return {m.group(1) for line in hlo_text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            for m in [re.search(r'op_name="[^"]*/(\w+)/pallas_call"', line)]
            if m}


def step_kernels(engine) -> set:
    """Compile the engine's step as its first served step ran (cached by
    now) and read which kernels it holds as Mosaic custom calls."""
    import jax.numpy as jnp

    _, b, w = engine.step_shapes[0]
    compiled = engine._jit_step.lower(
        engine.params, engine._prepared, engine._tables,
        engine._stream_state, engine._beam,
        jnp.zeros((b, w, engine._need), jnp.float32),
        jnp.zeros((b,), jnp.int32)).compile()
    return pallas_kernels(compiled.as_text())


def warm_up(engine, compiles) -> None:
    """Compile every step entry live traffic may run — each slot bucket
    at each window bucket — and the poll and finish readouts before the
    server opens: a compile while real-time clients stream makes them
    fall behind, and the backlog then asks for wider window buckets.
    `compiles` is the list `watch_compiles` fills."""
    import numpy as np

    for w in engine._buckets:
        audio = np.zeros(engine._need + (w - 1)
                         * engine.plan.samples_per_step, np.float32)
        for b in reversed(engine._slot_buckets):
            t, seen = time.perf_counter(), len(compiles)
            sessions = [engine.open() for _ in range(b)]
            for sess in sessions:
                sess.push(audio)
            sessions[0].poll()
            for sess in sessions:
                sess.finish()
            step = sum(secs for name, secs in compiles[seen:]
                       if name == "jit(step)")
            log(f"warm-up: step entry b={b} w={w} first run "
                f"{time.perf_counter() - t:.2f}s, backend compile "
                f"{step:.2f}s (0 when the persistent cache had it)")
    engine.step_shapes.clear()


def watch_compiles() -> list:
    """(function, seconds) of every backend compile from now on."""
    from jax import monitoring
    from jax._src import dispatch

    events = []

    def on_event(event, secs, **kw):
        if event == dispatch.BACKEND_COMPILE_EVENT:
            events.append((kw.get("fun_name", "?"), secs))

    monitoring.register_event_duration_secs_listener(on_event)
    return events


def log_compiles(events) -> None:
    """Each compile of a second or more, then the rest as one sum."""
    small = [secs for _, secs in events if secs < 1.0]
    for name, secs in events:
        if secs >= 1.0:
            log(f"compile {name}: {secs:.2f}s")
    log(f"compile: {len(small)} more under 1s, {sum(small):.2f}s in all")


async def _stream(host, port, audio, chunk, sr):
    """One client at real time: push 80 ms, poll, ...; then finish."""
    from repro.serving.server import AsrClient

    client = await AsrClient.open(host, port)
    t0 = time.perf_counter()
    first = None
    for i, off in enumerate(range(0, len(audio), chunk)):
        await asyncio.sleep(max(0.0, t0 + i * chunk / sr
                                - time.perf_counter()))
        res = await client.push(audio[off:off + chunk])
        if "error" not in res:
            res = await client.poll()
        if "error" in res:           # a fault ends the session's stream
            await client.aclose()
            return {"final": {}, "error": res["error"],
                    "first_result_s": first, "finalize_s": None}
        if first is None and res["steps"] > 0:
            first = time.perf_counter() - t0
    t1 = time.perf_counter()
    final = await client.finish()
    return {"final": final, "error": final.get("error"),
            "first_result_s": first, "finalize_s": time.perf_counter() - t1}


def serve_streams(engine, utts):
    """EngineServer in this process, one AsrClient per utterance."""
    from repro.serving.server import EngineServer, fetch_metrics

    chunk = engine.plan.samples_per_step
    sr = engine.program.feat_cfg.sample_rate

    async def run():
        server = EngineServer(asr_engine=engine)
        await server.start()
        try:
            out = await asyncio.gather(*(
                _stream(server.host, server.port, a, chunk, sr)
                for a in utts))
            return out, await fetch_metrics(server.host, server.port)
        finally:
            await server.aclose(drain=True, timeout=60.0)

    return asyncio.run(run())


def lockstep(engine, utts) -> list:
    """In-process decodes of `utts` side by side: each round pushes 80 ms
    to every session, then one poll steps all of them together."""
    chunk = engine.plan.samples_per_step
    sessions = [engine.open() for _ in utts]
    for off in range(0, max(len(a) for a in utts), chunk):
        for sess, audio in zip(sessions, utts):
            if off < len(audio):
                sess.push(audio[off:off + chunk])
        sessions[0].poll()
    return [sess.finish() for sess in sessions]


def single_stream(engine, audio) -> dict:
    """In-process decode of one stream, 80 ms pushes each followed by
    a poll (so every step runs one slot, one window)."""
    chunk = engine.plan.samples_per_step
    sess = engine.open()
    for off in range(0, len(audio), chunk):
        sess.push(audio[off:off + chunk])
        sess.poll()
    return sess.finish()


def logp_diffs(engine, audio) -> tuple:
    """Max |served - reference| TDS log-prob of one utterance, with the
    served path at default precision and traced under "highest"."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import features
    from repro.kernels.policy import KernelPolicy
    from repro.models import tds

    prog = engine.program
    cfg, fc = prog.tds_cfg, prog.feat_cfg
    sig = jnp.asarray(audio)[None]
    state = tds.init_batched_stream_state(cfg, 1)

    def path(name, kernels, use_pallas):
        def f(params, sig):
            feats = features.mfcc(sig, fc, use_pallas=use_pallas,
                                  kernels=kernels, hot=True)
            n = feats.shape[1] // cfg.total_subsample * cfg.total_subsample
            return tds.forward_batched(params, cfg, feats[:, :n], state,
                                       kernels=kernels)[0]
        f.__name__ = name
        return jax.jit(f)

    served = path("served_forward", engine.config.kernels, True)
    got = np.asarray(served(engine.params, sig))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(path("reference_forward", KernelPolicy("ref"),
                               False)(engine.params, sig))
        got_f32 = np.asarray(served(engine.params, sig))
    return float(np.abs(got - want).max()), float(np.abs(got_f32
                                                          - want).max())


def hypothesis_unit_check(policy, beam, fanout, seed) -> list:
    """The hypothesis unit on 8 full-width candidate rows under the
    engine's policy vs its jnp twin: same valid masks, and the selected
    totals agree (a near-tie may swap two picks, never their scores)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    from repro.kernels.policy import KernelPolicy

    rng = np.random.default_rng(seed)
    n = beam * (2 * fanout + 1)
    hashes = jnp.asarray(rng.integers(0, n // 2, (8, n)), jnp.int32)
    dead = rng.random((8, n)) < 0.2
    pb, pnb = (jnp.asarray(np.where(dead, -1e30, rng.normal(0, 3, (8, n))),
                           jnp.float32) for _ in range(2))
    outs = [ops.hypothesis_unit(hashes, pb, pnb, beam, 25.0, policy=p)
            for p in (policy, KernelPolicy("ref"))]
    problems = []
    if not np.array_equal(np.asarray(outs[0]["valid"]),
                          np.asarray(outs[1]["valid"])):
        problems.append("hypothesis_unit: valid masks differ from ref")
    tot = [np.asarray(jnp.logaddexp(o["pb"], o["pnb"])) for o in outs]
    if not np.allclose(tot[0], tot[1], rtol=1e-5, atol=1e-4):
        problems.append(f"hypothesis_unit: totals differ from ref by "
                        f"{np.abs(tot[0] - tot[1]).max()}")
    return problems


def bucket_witness(engine, utts) -> list:
    """Side-by-side decodes (the largest slot bucket) vs single-stream
    ones (bucket 1), at default precision and under "highest"."""
    import contextlib

    import jax

    problems = []
    for name, ctx, rtol, tokens in (
            ("default", contextlib.nullcontext, SCORE_RTOL, False),
            ("highest", lambda: jax.default_matmul_precision("highest"),
             SCORE_RTOL_F32, True)):
        with ctx():
            together = lockstep(engine, utts)
            alone = [single_stream(engine, a) for a in utts]
        gaps = [score_gap(a, b) for a, b in zip(together, alone)]
        same = sum(a["tokens"].tolist() == b["tokens"].tolist()
                   for a, b in zip(together, alone))
        log(f"side by side vs single-stream, {name} precision: score gap "
            f"max {max(gaps)} relative (tol {rtol}), tokens equal in "
            f"{same} of {len(utts)}")
        for i, (a, b) in enumerate(zip(together, alone)):
            if not same_transcript(a, b, rtol, tokens):
                log(compare_line(i, ("side by side", "single-stream"), a, b))
                problems.append(f"stream {i}: side-by-side and single-stream "
                                f"decodes differ at {name} precision")
    return problems


def memory_line(devs) -> str:
    parts = []
    for d in devs:
        stats = d.memory_stats() or {}
        parts.append(f"{d.id}:{stats.get('peak_bytes_in_use', 'n/a')}")
    return "peak_bytes_in_use per device " + " ".join(parts)


def one_chip(seed: int) -> list:
    import numpy as np

    from repro.data.pipeline import SyntheticASR

    compiles = watch_compiles()
    t = time.perf_counter()
    system = build_system(seed)
    engine = build_engine(SLOTS, system)
    log(f"system built in {time.perf_counter() - t:.1f}s: "
        f"{len(system[1])} words, vocab {system[0].vocab_size}, beam "
        f"{system[5].beam_size}, fanout {system[5].max_children}")
    data = SyntheticASR(system[1], seed=seed)
    utts = [data.utterance(i, n_words=WORDS_PER_UTT)["audio"]
            for i in range(STREAMS)]
    sr = engine.program.feat_cfg.sample_rate

    warm_up(engine, compiles)
    problems = []
    results, metrics = serve_streams(engine, utts)
    for i, r in enumerate(results):
        log(f"stream {i}: {len(utts[i]) / sr:.2f}s audio, first result "
            f"{r['first_result_s']}s, finalize {r['finalize_s']}s, "
            f"words {r['final'].get('words')}, "
            f"{len(r['final'].get('tokens', []))} tokens")
        if r["error"] or "words" not in r["final"]:
            problems.append(f"stream {i}: {r['error'] or r['final']}")
    faulted = metrics["asr"]["sessions"]["faulted"]
    if faulted or engine._fault_log:
        problems.append(f"faulted sessions {faulted}, fault log "
                        f"{engine._fault_log[:3]}")
    log(f"served step shapes (n, b, w), first seen: "
        f"{list(dict.fromkeys(engine.step_shapes))}")

    found = step_kernels(engine)
    log(f"Mosaic kernels in the compiled step: {sorted(found)}")
    missing = set(KERNELS) - found
    if missing:
        problems.append(f"no Mosaic custom call for {sorted(missing)}")

    served, f32 = logp_diffs(engine, utts[0])
    log(f"TDS log-probs vs f32 reference: max |diff| {served} served "
        f"(tol {LOGP_TOL_SERVED}), {f32} under highest (tol "
        f"{LOGP_TOL_F32})")
    if not (served <= LOGP_TOL_SERVED and f32 <= LOGP_TOL_F32):
        problems.append("TDS log-probs differ from the f32 reference")

    problems += hypothesis_unit_check(
        engine.config.kernels, system[5].beam_size,
        system[5].max_children, seed)

    for i, (r, audio) in enumerate(zip(results, utts)):
        if "words" not in r["final"]:
            continue
        got = {k: np.asarray(v) for k, v in r["final"].items()}
        want = single_stream(engine, audio)
        log(compare_line(i, ("served", "single-stream"), got, want))
        if not same_transcript(got, want):
            problems.append(f"stream {i}: served and single-stream "
                            f"transcripts differ")
    problems += bucket_witness(engine, utts)
    log_compiles(compiles)
    log(memory_line(devices()[:1]))
    return problems


def four_chips(seed: int) -> list:
    from repro.data.pipeline import SyntheticASR
    from repro.parallel.sharding import make_mesh

    compiles = watch_compiles()
    devs = devices()
    if len(devs) < 4:
        return [f"--chips 4 needs 4 devices, found {len(devs)}"]
    system = build_system(seed)
    mesh = make_mesh((2, 2), ("data", "model"))
    engines = {"2x2": build_engine(MESH_STREAMS, system, mesh=mesh),
               "1 chip": build_engine(MESH_STREAMS, system)}
    data = SyntheticASR(system[1], seed=seed)
    utts = [data.utterance(i, n_words=WORDS_PER_UTT)["audio"]
            for i in range(MESH_STREAMS)]
    out = {}
    for name, engine in engines.items():
        out[name] = lockstep(engine, utts)
        log(f"{name}: step shapes (n, b, w), first seen: "
            f"{list(dict.fromkeys(engine.step_shapes))}")
    problems = []
    for i, (a, b) in enumerate(zip(out["2x2"], out["1 chip"])):
        log(compare_line(i, ("2x2", "1 chip"), a, b))
        if not same_transcript(a, b):
            problems.append(f"stream {i}: 2x2 and 1-chip decodes differ")
    for name, engine in engines.items():
        if engine._fault_log:
            problems.append(f"{name}: fault log {engine._fault_log[:3]}")
    log_compiles(compiles)
    log(memory_line(devs))
    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devs = devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform}",
              file=sys.stderr)
        return 2
    from repro.runtime import compile_cache

    log(f"device {devs[0].device_kind} x{len(devs)}; compile cache "
        f"{compile_cache.use_persistent_cache()}")
    problems = (four_chips if args.chips == 4 else one_chip)(args.seed)
    for p in problems:
        log(f"FAIL {p}")
    if problems:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
