"""Offline decoding of whole recordings through the server's engine
worker, without HTTP: `repro.serving.server.EngineWorker`, the thread
that runs `EngineServer`'s commands and the engine's admit -> step ->
harvest loop, drives the engine, and the clients hand it commands with
`submit` and `watch_done`, as the server's request handlers do.

A closed loop: `clients_per_slot` clients per slot; each hands one whole
utterance to a session in a single push, finishes it, and takes the next
as soon as the worker has harvested its result.  The mix's `files`
utterances are made before the window and taken in the seed's order.

Audio decoded in a span: each utterance's audio is spread evenly over
its time in the engine, from the command that opened it to its harvest,
and the share of that time inside the span is counted.  In the closed
loop every slot decodes all the time, so this is the audio the span
decoded, without the error of counting whole utterances at its edges.
The clients keep the pool full until every utterance opened in the
window has been harvested (a minute at most), then take no more."""
from __future__ import annotations

import json
import threading
import time

import jax

import traffic as tr
from audio import utterance

WAIT_AFTER_S = 60.0


def overlap(t0: float, t1: float, a: float, b: float) -> float:
    """Share of [t0, t1] that lies inside [a, b]."""
    if t1 <= t0:
        return float(a <= t0 <= b)
    return max(0.0, min(t1, b) - max(t0, a)) / (t1 - t0)


def decoded_s(records: list, lens: list, a: float, b: float) -> float:
    """Audio seconds decoded in [a, b] by the harvested utterances."""
    return sum(lens[r["index"]] * overlap(r["t0"], r["t1"], a, b)
               for r in records if r["result"] is not None)


class Clients:
    """The closed loop's clients, driven from the worker's own thread:
    each harvest opens the next utterance."""

    def __init__(self, worker, audios: list):
        self.worker = worker
        self.audios = audios
        self.lock = threading.Lock()
        self.records: list = []
        self.next = 0
        self.stopped = False

    def start(self) -> None:
        with self.lock:
            if self.stopped or not self.worker.is_alive():
                return
            i = self.next % len(self.audios)
            self.next += 1
            rec = {"index": i, "t0": None, "t1": None, "result": None,
                   "error": None}
            self.records.append(rec)

        def open_one(engine, audio=self.audios[i]):
            with jax.profiler.TraceAnnotation("bench.submit"):
                rec["t0"] = time.monotonic()
                sess = engine.open()
                sess.push(audio)
                sess.finish(wait=False)
            return sess

        self.worker.submit(open_one).add_done_callback(
            lambda f: self._opened(rec, f))

    def _opened(self, rec: dict, fut) -> None:
        if fut.exception() is not None:
            self._done(rec, fut)
            return
        self.worker.watch_done(fut.result()).add_done_callback(
            lambda f: self._done(rec, f))

    def _done(self, rec: dict, fut) -> None:
        rec["t1"] = time.monotonic()
        if fut.exception() is not None:
            rec["error"] = repr(fut.exception())
        else:
            rec["result"] = fut.result()
        self.start()

    def in_flight(self, opened_before: float = float("inf")) -> int:
        with self.lock:
            return sum(r["t1"] is None and (r["t0"] is None
                                            or r["t0"] < opened_before)
                       for r in self.records)


def _counters(worker) -> dict:
    def snap(engine):
        m = engine.metrics
        return {"steps": m.steps, "stepped_slots": m.stepped_slots,
                "dispatched_rows": m.dispatched_rows,
                "admitted": m.admitted, "finalized": m.finalized,
                "faulted": m.faulted_sessions}
    return worker.submit(snap).result()


def _sleep_until(t: float) -> None:
    while True:
        left = t - time.monotonic()
        if left <= 0:
            return
        time.sleep(min(left, 0.05))


def run(engine, mix: dict, seed: int, seconds: float, tracer, log) -> dict:
    from repro.serving.server import EngineWorker

    lens = tr.bulk_lengths(mix, seed)
    audios = [utterance(seed, i, s) for i, s in enumerate(lens)]
    log(f"bulk: {len(lens)} files, lengths "
        f"{json.dumps(tr.describe(lens))}")
    worker = EngineWorker(engine, name="bench-engine-worker")
    clients = Clients(worker, audios)
    try:
        t_open = time.monotonic()
        for _ in range(engine.n_slots * mix["clients_per_slot"]):
            clients.start()
        w0 = t_open + mix["ramp_s"]
        w1 = w0 + seconds
        _sleep_until(w0)
        w0 = time.monotonic()
        c0 = _counters(worker)
        trace_span = None
        if tracer is not None:
            _sleep_until(w0 + mix["trace_at_s"])
            tracer.start()
            _sleep_until(tracer.t0 + mix["trace_s"])
            tracer.stop()
            trace_span = (tracer.t0, tracer.t1)
        _sleep_until(w1)
        w1 = time.monotonic()
        c1 = _counters(worker)
        deadline = w1 + WAIT_AFTER_S
        while clients.in_flight(w1) and time.monotonic() < deadline:
            time.sleep(0.01)
        with clients.lock:
            clients.stopped = True
        while clients.in_flight() and time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        worker.close()
    recs = [r for r in clients.records
            if r["t0"] is not None and r["t0"] < w1
            and (r["t1"] is None or r["t1"] > w0)]
    failed = sum(r["result"] is None for r in recs)
    audio_s = decoded_s(recs, lens, w0, w1)
    served = [{"index": r["index"], "seconds": lens[r["index"]],
               "final": {k: (float(v) if k == "score" else
                             [int(x) for x in v])
                         for k, v in r["result"].items()
                         if k in ("tokens", "words", "score")}}
              for r in recs if r["result"] is not None]
    counters = {k: c1[k] - c0[k] for k in c0}
    log(f"bulk: window {w1 - w0:.3f}s, {len(recs)} utterances in it, "
        f"{audio_s:.3f} s of audio decoded in it, {failed} failed; engine "
        f"counters over it {json.dumps(counters)}")
    out = {"w0": w0, "window_s": w1 - w0, "attempted": len(recs),
           "failed": failed, "audio_s": audio_s, "served": served}
    if trace_span is not None:
        out["trace_audio_s"] = decoded_s(recs, lens, *trace_span)
    return out
