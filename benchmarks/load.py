"""Load/SLO harness for the network serving front-end.

Boots an in-process `EngineServer` over the demo ASR engine
(`repro.launch.serve.asr_demo_engine`) and replays N concurrent
staggered synthetic utterance streams against it through the real wire
protocol (`AsrClient`: HTTP chunked push/poll/finish).  Reports, per
group:

  * first-result latency p50/p95/p99 — client-observed time from
    opening the stream to the first poll whose hypothesis covers a
    decoded step
  * finalize latency p50/p95/p99 — finish() round-trip to the final
    transcript
  * throughput (completed utterances/s and x realtime audio)
  * rejection rate + engine-side max queue depth (the backpressure
    policy under overload: sessions beyond `--max-queue` get 503)

  PYTHONPATH=src python -m benchmarks.load --streams 100 --slots 8 \\
      --json BENCH_load.json
  PYTHONPATH=src python -m benchmarks.load --streams 48 --slots 2 \\
      --max-queue 4 --stagger-ms 0 --group overload --json BENCH_load.json

Rows are written/merged into the ``--json`` mapping as
``<group>_<metric>`` keys (same contract as benchmarks/run.py);
benchmarks/compare.py ``--load`` annotates p95 regressions between a
committed BENCH_load.json and a fresh run.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import pathlib
import time

import numpy as np

ROWS = {}


def row(name: str, value, unit: str = ""):
    # integer counters (stream/slot/queue-depth counts) round-trip as
    # JSON ints — emitting them as 8.0/100.0 made compare.py --load
    # diffs format-drift against hand-read baselines
    if isinstance(value, (int, np.integer)) and not isinstance(value, bool):
        ROWS[name] = int(value)
    else:
        ROWS[name] = round(float(value), 4)
    print(f"{name},{ROWS[name]}{',' + unit if unit else ''}", flush=True)


def _pct(seconds: list, q: float) -> float:
    return float(np.percentile(np.asarray(seconds, float), q)) * 1e3


async def _run_stream(host: str, port: int, audio: np.ndarray,
                      chunk: int, stagger_s: float, realtime: bool,
                      retries: int = 0, backoff: float = 0.05,
                      seed: int = 0) -> dict:
    """One client: staggered open (with optional jittered retry on
    503), chunked pushes with a poll after each, finish; returns
    client-observed latencies (or the rejection / fault)."""
    from repro.serving.server import AsrClient, ServerRejected

    await asyncio.sleep(stagger_s)
    t0 = time.perf_counter()
    try:
        try:
            client = await AsrClient.open(host, port, retries=retries,
                                          backoff=backoff, seed=seed)
        except ServerRejected:
            return {"rejected": True}
        first = None
        for off in range(0, len(audio), chunk):
            res = await client.push(audio[off:off + chunk])
            if res.get("error"):
                return {"rejected": False, "faulted": True,
                        "error": res["error"]}
            res = await client.poll()
            if res.get("error"):
                return {"rejected": False, "faulted": True,
                        "error": res["error"]}
            if first is None and res["steps"] > 0:
                first = time.perf_counter() - t0
            if realtime:
                await asyncio.sleep(chunk / 16000.0)
        t_fin = time.perf_counter()
        final = await client.finish()
        t_end = time.perf_counter()
        if final.get("error"):
            return {"rejected": False, "faulted": True,
                    "error": final["error"]}
    except ConnectionError:
        return {"rejected": True}
    if first is None:            # tail-flush produced the only step
        first = t_end - t0
    return {"rejected": False, "faulted": False, "first_result_s": first,
            "finalize_s": t_end - t_fin, "e2e_s": t_end - t0,
            "audio_s": len(audio) / 16000.0, "steps": final["steps"]}


async def _run_load(args) -> dict:
    from repro.data.pipeline import SyntheticASR
    from repro.launch.serve import asr_demo_engine
    from repro.serving.server import EngineServer, fetch_metrics

    engine, words = asr_demo_engine(args.slots, max_queue=args.max_queue)
    data = SyntheticASR(words)
    utts = [data.utterance(i % 16)["audio"] for i in range(args.streams)]
    chunk = max(1, int(16000 * args.chunk_ms / 1000.0))

    server = EngineServer(asr_engine=engine, host="127.0.0.1", port=0)
    await server.start()
    try:
        # warmup wave (excluded from stats): traces the fused-step jit
        # buckets the measured wave will hit, so the report shows
        # steady-state serving latency, not first-use compile time
        n_warm = args.slots if args.warmup is None else args.warmup
        if n_warm:
            await asyncio.gather(*[
                _run_stream(server.host, server.port,
                            utts[i % len(utts)], chunk, i * 0.01, False)
                for i in range(n_warm)])
        pre = (await fetch_metrics(server.host, server.port))["asr"]
        t0 = time.perf_counter()
        outs = await asyncio.gather(*[
            _run_stream(server.host, server.port, audio, chunk,
                        i * args.stagger_ms / 1000.0, args.realtime,
                        retries=args.retries, backoff=args.backoff, seed=i)
            for i, audio in enumerate(utts)])
        wall = time.perf_counter() - t0
        metrics = (await fetch_metrics(server.host, server.port))["asr"]
    finally:
        await server.aclose()
    rejected_in_run = (metrics["sessions"]["rejected"]
                       - pre["sessions"]["rejected"])
    return {"outs": outs, "wall": wall, "metrics": metrics,
            "rejected_in_run": rejected_in_run}


def report(args, res: dict) -> None:
    g = args.group
    outs, wall, metrics = res["outs"], res["wall"], res["metrics"]
    n_faulted = sum(1 for o in outs if o.get("faulted"))
    done = [o for o in outs
            if not o["rejected"] and not o.get("faulted")]
    n_rejected = len(outs) - len(done) - n_faulted
    assert done, "every stream was rejected — raise --max-queue"

    row(f"{g}_streams", len(outs))
    row(f"{g}_slots", args.slots)
    for metric in ("first_result", "finalize"):
        vals = [o[f"{metric}_s"] for o in done]
        for q in (50, 95, 99):
            row(f"{g}_{metric}_p{q}_ms", _pct(vals, q), "ms")
    row(f"{g}_e2e_p95_ms", _pct([o["e2e_s"] for o in done], 95), "ms")
    row(f"{g}_wall_s", wall, "s")
    row(f"{g}_throughput_utt_per_s", len(done) / wall)
    row(f"{g}_throughput_x_realtime",
        sum(o["audio_s"] for o in done) / wall)
    row(f"{g}_rejection_rate", n_rejected / len(outs))
    row(f"{g}_faulted", n_faulted)
    row(f"{g}_max_queue_depth", metrics["queue"]["max_depth"])
    row(f"{g}_occupancy", metrics["steps"]["occupancy"] or 0.0)
    if args.max_queue is not None:
        # the backpressure invariant the SLO story rests on (also
        # pinned by tests): overload bounds the queue, never grows it
        assert metrics["queue"]["max_depth"] <= args.max_queue, metrics
        if args.retries == 0:
            # with retries, each 503'd attempt bumps the server-side
            # rejected counter, so it can exceed client-observed fails
            assert res["rejected_in_run"] == n_rejected, \
                (metrics["sessions"], n_rejected)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--streams", type=int, default=100,
                    help="concurrent client streams to replay")
    ap.add_argument("--slots", type=int, default=8,
                    help="ASR engine slot-pool size")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="EngineConfig.max_queue backpressure bound "
                         "(default: unbounded — no rejections)")
    ap.add_argument("--stagger-ms", type=float, default=20.0,
                    help="arrival stagger between consecutive streams")
    ap.add_argument("--chunk-ms", type=float, default=80.0,
                    help="audio chunk size per push")
    ap.add_argument("--warmup", type=int, default=None,
                    help="warmup streams run (and discarded) before the "
                         "measured wave, to trace the jit step buckets "
                         "(default: one per slot)")
    ap.add_argument("--retries", type=int, default=0,
                    help="client-side retry attempts on 503/connection "
                         "failure (jittered exponential backoff; "
                         "default: fail fast, counted as rejection)")
    ap.add_argument("--backoff", type=float, default=0.05,
                    help="base backoff delay in seconds for --retries")
    ap.add_argument("--realtime", action="store_true",
                    help="pace each stream at realtime (sleep one chunk "
                         "duration per push) instead of replaying as "
                         "fast as the server accepts")
    ap.add_argument("--group", default="load",
                    help="row-name prefix in the JSON output")
    ap.add_argument("--json", type=pathlib.Path, default=None,
                    help="merge rows into this JSON mapping")
    args = ap.parse_args(argv)
    from repro.runtime import compile_cache
    compile_cache.use_persistent_cache()

    res = asyncio.run(_run_load(args))
    report(args, res)
    if args.json:
        merged = {}
        if args.json.exists():
            merged = json.loads(args.json.read_text())
        merged.update(ROWS)
        args.json.write_text(json.dumps(merged, indent=1, sort_keys=True)
                             + "\n")
        print(f"wrote {len(ROWS)} rows to {args.json}")
    return ROWS


if __name__ == "__main__":
    main()
