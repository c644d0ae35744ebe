"""Streaming ASR engine: B utterance slots, ONE slot-native decoding step.

The fused decoding step (paper §3.1: acoustic scoring — the front end
and the acoustic model — then one hypothesis expansion per emitted
acoustic frame) is pure in all carried state, and slot-native END TO
END.  The acoustic model comes from the program's acoustic configuration
through the contract of `models/acoustic.py` (`AsrProgram.model`): the
paper's TDS (MFCC front end, one CTC frame per 80 ms window) or the
cache-aware streaming Conformer (log-mel front end, a 14-frame chunk per
1.12 s window).  The step calls the model's `forward_batched`, resets a
slot with its `reset_stream_slot` and builds the pool with its
`init_batched_stream_state`; the window's length is the model's own
(`AsrProgram.step_ms`, `make_step_plan`).  The slot axis folds into the
row dimension of every matmul (no per-slot vmap), the front end's tail
is the fused logmel kernel, int8 TDS programs use weights pre-quantized
ONCE at engine build (`AsrProgram.prepare_params`), and hypothesis
expansion is natively slot-batched (`decoder.expand_step_batched`): the
shared lexicon trie / bigram table are gathered once over the flattened
slot index set and the fused Pallas hypothesis unit runs with a batch
grid axis.  Every pytree leaf of the model's carried state and of the
`BeamState` carries a leading slot axis, each slot keeps its own sample
buffer, and one jitted step advances every slot that has a full window
buffered.  Slots without a window are masked out — their carried state
passes through unchanged — so each slot's trajectory is exactly the
single-stream decoder's.

Window bookkeeping is the setup-thread arithmetic from core/features:
`frames_producible` decides whether a slot can step (enough buffered
samples for plan.feat_frames_per_step whole frames) and
`consumed_samples` decides how many samples a step retires (the
framing overlap stays buffered).  When a slot has several whole windows
buffered (bulk decoding — `serve(utterances)`), one fused step consumes
up to `AsrProgram.max_windows_per_step` of them at once: each window's
samples are extracted exactly as a w=1 step would see them, and the
model carries its state across the windows of one step as across
steps, so the fold matches stepping windows one at a time (bit-for-bit
for TDS), but every weight matrix is read once per multi-window step
instead of once per window (the acoustic forward is
weight-bandwidth-bound at B=1).

Each step runs on a GATHERED sub-batch, not the full masked pool: the
scheduler picks the window count w maximizing retired windows
(w x eligible slots, largest w on ties), gathers exactly the eligible
slots into the smallest covering slot bucket (powers of two up to
n_slots), and scatters their new state back.  Slots holding fewer than
w windows step in catch-up dispatches of the same pump round (the same
rule over the slots not yet stepped), so every slot holding a window
steps once per round.  Slots outside a dispatch are simply never
written — per-slot trajectories are untouched (the acoustic
forward and the expansion are row-independent in the slot axis, pinned
bitwise by tests).  The old full-pool masked step paid B=n_slots
compute however few slots were eligible, which made the ragged tail of
a utterance batch SLOWER than sequential decoding (a one-eligible-slot
w=4 step cost ~4x its B=1 equivalent; see BENCH_decode.json's
serve_asr_batched_b4 history).

With `EngineConfig.mesh` set (a Mesh with a 'model' axis; TDS only),
the fused step runs under `shard_map`: FC/head weights live as
feature-axis shards (`AsrProgram.prepare_params` places them), each device contracts
its shard and psums partial products (`tds.forward_batched(axis=)`),
and everything else — convs, LayerNorms, MFCC, hypothesis expansion —
stays replicated.  mesh=None is the exact single-device path.

A 2D ('data', 'model') mesh additionally shards the SLOT POOL: each
data shard owns n_slots/n_data contiguous slots — their TDS
left-context state, beam, and gathered sub-batch rows
(`parallel.sharding.asr_state_specs`) — and steps them end-to-end
without any 'data'-axis collective (beam expansion is embarrassingly
parallel across slots; only the 'model'-axis matmul psums remain).
The scheduler keeps the gather/scatter shard-aligned: eligible slots
group by home shard, every shard runs the same per-shard pow-2 bucket,
and pad rows carry index -1 so their garbage update is dropped on
scatter-back.  Per-slot trajectories stay bit-identical to mesh=None.
`EngineConfig.overlap_psum` swaps the model-axis psums for the
latency-hiding output-column split (`ops.psum_overlap_matmul`).

Two API layers:
  * slot level — `feed_slot` / `pump` / `slot_best` / `reset_slot`:
    direct slot addressing for the deprecated ASRPU command shims
    (core/scheduler.py).  Do not mix with sessions on the same engine.
  * session level — `open()` -> Session.push/poll/finish, plus the
    `serve(utterances)` convenience (continuous batching over whole
    utterances, results in input order).
"""
from __future__ import annotations

from collections import deque
from typing import List

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import decoder as dec
from repro.core import features
from repro.analysis.guards import no_implicit_transfers
from repro.serving.config import AsrProgram, EngineConfig
from repro.serving.engine import (Engine, Session, SessionFaulted,
                                 copy_result, worker_only)


def empty_hypothesis() -> dict:
    """Readout when no beam exists yet (nothing decoded): same keys as a
    real `decoder.materialize_best` payload, -inf score."""
    return {"words": np.zeros((0,), np.int32),
            "tokens": np.zeros((0,), np.int32), "score": -np.inf}


class AsrEngine(Engine):
    def __init__(self, config: EngineConfig, params):
        assert isinstance(config.program, AsrProgram), config.program
        super().__init__(config)
        self.program: AsrProgram = config.program
        self.plan = self.program.step_plan()
        fc = self.program.feat_cfg
        nfr = self.plan.feat_frames_per_step
        # samples retired per step / needed buffered for a full window
        self._spp = features.consumed_samples(nfr, fc)
        self._need = fc.frame_len + (nfr - 1) * fc.frame_shift
        # samples a step retains for MFCC framing overlap: buffered
        # samples beyond this were never covered by a decoded frame
        self._overlap = self._need - self._spp
        assert self._spp == self.plan.samples_per_step, \
            (self._spp, self.plan.samples_per_step)
        assert features.frames_producible(self._need, fc) == nfr
        mesh = config.mesh
        # 2D ('data','model') mesh: the slot pool itself is sharded —
        # each data shard owns n_slots/n_data contiguous pool slots
        # (slot s lives on shard s // slots_per_shard) and carries them
        # end-to-end through the fused step; 'model' keeps PR 5's
        # feature-axis weight shards.  mesh=None / 1D stay the exact
        # replicated-pool paths.
        self._data_axis = ("data" if mesh is not None
                           and "data" in mesh.axis_names else None)
        self._n_data = mesh.shape["data"] if self._data_axis else 1
        self._slots_per_shard = self.n_slots // self._n_data
        # per-step batch/idx uploads are placed EXPLICITLY with the
        # step's in_specs sharding: jnp.asarray would commit them to one
        # device and every dispatch would then reshard them through an
        # implicit transfer (caught by no_implicit_transfers(strict=True))
        if mesh is not None:
            dspec = ((P("data", None, None), P("data"))
                     if self._data_axis else (P(), P()))
            self._input_shardings = tuple(
                NamedSharding(mesh, s) for s in dspec)
        else:
            self._input_shardings = None
        self._buckets = self.program.step_buckets()
        self._slot_buckets = self._make_slot_buckets()
        # int8 weights are quantized exactly ONCE, here — the decoding
        # step then only quantizes activations (ops.int8_matmul_prepared)
        # — and, under a mesh, weights are PLACED as feature-axis shards
        self.params, self._prepared = self.program.prepare_params(
            params, config.mesh)
        # the lexicon trie and LM enter the jitted programs as arguments
        # (replicated under a mesh), never as closed-over constants
        self._tables = (self.program.lex, self.program.lm)
        if mesh is not None:
            self._tables = jax.device_put(
                self._tables, NamedSharding(mesh, P()))
        self._jit_step = self._build_step()
        self._jit_reset = jax.jit(self._reset_slot_fn())
        self._jit_best = jax.jit(self._slot_best_fn(final=False))
        self._jit_best_final = jax.jit(self._slot_best_fn(final=True))
        self._reset_pool()

    # ---- the fused decoding-step program -----------------------------
    def _make_slot_buckets(self):
        """Ascending PER-SHARD sub-batch sizes a gathered step may run
        at (powers of two, topped by slots_per_shard) — one jit entry
        per (b, w) pair, traced lazily, mirroring
        `AsrProgram.step_buckets`.  Without a 'data' mesh axis,
        slots_per_shard == n_slots and these are the total sub-batch
        sizes as before; with one, the dispatched batch is
        bucket * n_data rows (every shard steps the same local bucket,
        so the gather/scatter stays shard-aligned — a multiple of
        n_data by construction)."""
        out, b = [], 1
        while b < self._slots_per_shard:
            out.append(b)
            b *= 2
        out.append(self._slots_per_shard)
        return tuple(sorted(set(out)))

    def step_entries(self) -> list:
        """(slot bucket, window bucket) of every jit entry the fused step
        can run, largest first: a warm-up that steps b slots holding w
        windows each, for each pair, compiles every entry once."""
        return [(b, w) for w in self._buckets
                for b in reversed(self._slot_buckets)]

    def _step_fn(self):
        """One slot-native decoding step over a GATHERED sub-batch:
        acoustic scoring (the front end's fused logmel tail + the
        acoustic model's `forward_batched`) runs natively over the
        gathered slot axis — the slot axis folds into the rows of every
        matmul — then each emitted acoustic frame runs ONE natively
        batched hypothesis expansion (shared lexicon/LM gathers over the
        flattened slot index set + the fused hypothesis unit).  Only
        the gathered slots are written back; every other slot's carried
        state is untouched."""
        prog = self.program
        model = prog.model
        nfr = self.plan.feat_frames_per_step
        kernels = self.config.kernels
        axis = "model" if self.config.mesh is not None else None
        data_axis = self._data_axis
        spshard = self._slots_per_shard
        overlap = self.config.overlap_psum

        def step(params, prepared, tables, stream_state, beam_state, samples,
                 slots):
            # samples: (b, w, samples_per_window) — w buffered
            # windows for each of the b gathered slots, extracted window
            # by window (each row is exactly the signal a w=1 step would
            # see, so fusing windows is bit-identical to stepping them
            # one at a time).  slots: (b,) int32 pool indices; bucket
            # padding repeats a real slot, whose duplicate rows compute
            # an identical update, so the scatter-back stays exact.
            #
            # With a 'data' mesh axis, this body sees one data shard's
            # view: stream_state/beam_state are its slots_per_shard
            # local pool rows, samples/slots its rows of the gathered
            # sub-batch.  slots stay GLOBAL pool indices (shard d owns
            # [d*spshard, (d+1)*spshard)); bucket padding is -1 — pad
            # rows gather local row 0, compute a garbage update, and
            # are dropped by the out-of-range scatter, so every real
            # slot's trajectory is bit-identical to the unsharded step.
            b, w, _ = samples.shape
            if data_axis is not None:
                d = jax.lax.axis_index(data_axis)
                loc = slots - d * spshard
                valid = slots >= 0
                gidx = jnp.where(valid, loc, 0)
            else:
                gidx = slots
            ss = jax.tree.map(lambda a: a[gidx], stream_state)
            bs = jax.tree.map(lambda a: a[gidx], beam_state)
            # the named scopes label the device trace's ops by stage
            # (op metadata only: HLO instruction names do not change)
            with jax.named_scope(model.FRONT_END):
                feats = model.front_end(samples, prog.feat_cfg,
                                        kernels=kernels)[:, :, :nfr]
                feats = feats.reshape(b, w * nfr, -1)
            with jax.named_scope(model.SCOPE):
                logp, new_ss = model.forward_batched(
                    params, prog.acoustic, feats, ss,
                    use_int8=prog.use_int8, kernels=kernels,
                    prepared=prepared, axis=axis, overlap=overlap)

            lex, lm = tables

            def expand(bst, lp):           # lp: (b, V) — one frame, all slots
                return dec.expand_step_batched(bst, lp, lex, lm,
                                               prog.dec_cfg, kernels), None
            with jax.named_scope("expand"):
                new_bs, _ = jax.lax.scan(expand, bs,
                                         jnp.swapaxes(logp, 0, 1))
            with jax.named_scope("writeback"):
                # keep the expansion's gathers out of the scatter-back
                # fusions: fused into them at 2- and 4-row sub-batches,
                # the TPU compiler aborts (fusion_emitter: "Check failed:
                # GetGatherType(gather) == GatherType::kSublaneGather")
                new_ss, new_bs = jax.lax.optimization_barrier(
                    (new_ss, new_bs))

                if data_axis is not None:
                    # out-of-range rows (pad, or another shard's slot —
                    # the scheduler never builds those) drop instead of
                    # writing
                    widx = jnp.where(valid, loc, spshard)

                    def put(full, new):
                        return full.at[widx].set(new, mode="drop")
                else:
                    def put(full, new):
                        return full.at[slots].set(new)
                return (jax.tree.map(put, stream_state, new_ss),
                        jax.tree.map(put, beam_state, new_bs))

        return step

    def _build_step(self):
        """jit the fused step; with a mesh (TDS only: `EngineConfig`
        refuses one for other models), wrap it in `shard_map` so each
        device reads only its FC/head weight shard (psum-reduced
        contractions inside `tds.forward_batched`).  On a 1D ('model',)
        mesh, slot state, samples, and the expansion stay replicated
        (PR 5's layout, bitwise-preserved); on a 2D ('data','model')
        mesh, the pool state and the gathered sub-batch are sharded on
        their slot axis over 'data' (`asr_state_specs`) and come back
        out still sharded — expansion is slot-parallel, so the step has
        no 'data'-axis collectives at all."""
        step = self._step_fn()
        mesh = self.config.mesh
        if mesh is None:
            return jax.jit(step)
        from repro.parallel import sharding as shlib
        prog = self.program
        pspecs = shlib.tds_param_specs(prog.acoustic, mesh)
        qspecs = (shlib.tds_prepared_specs(prog.acoustic, mesh)
                  if self._prepared is not None else P())
        if self._data_axis is not None:
            ss_t, bs_t = jax.eval_shape(
                lambda: (prog.model.init_batched_stream_state(
                            prog.acoustic, self.n_slots),
                         dec.init_batched_state(
                            self.n_slots, self.program.dec_cfg.beam_size,
                            self.program.lm)))
            sspecs = shlib.asr_state_specs(ss_t, mesh)
            bspecs = shlib.asr_state_specs(bs_t, mesh)
            return jax.jit(jax.shard_map(
                step, mesh=mesh,
                in_specs=(pspecs, qspecs, P(), sspecs, bspecs,
                          P("data", None, None), P("data")),
                out_specs=(sspecs, bspecs), check_vma=False))
        rep = P()
        return jax.jit(jax.shard_map(
            step, mesh=mesh,
            in_specs=(pspecs, qspecs, rep, rep, rep, rep, rep),
            out_specs=(rep, rep), check_vma=False))

    def _reset_slot_fn(self):
        """One fused slot reset (utterance boundary): writing the model's
        fresh carried state + beam leaves slot-by-slot in eager mode
        costs an un-jitted scatter per pytree leaf, which dominated
        sequential serving; fusing them makes admission O(one
        dispatch)."""
        prog = self.program

        def reset(stream_state, beam, slot):
            return (prog.model.reset_stream_slot(stream_state, slot,
                                                 prog.acoustic),
                    dec.reset_slot(beam, slot, prog.lm))

        return reset

    def _slot_best_fn(self, final: bool):
        """Fused slot-slice (+ optional finalize) + argmax readout: the
        eager version paid one dispatch per BeamState leaf per poll."""
        prog = self.program

        def readout(tables, beam, slot):     # "jit_readout" in a trace
            st = dec.slot_state(beam, slot)
            if final:
                st = dec.finalize(st, *tables, prog.dec_cfg)
            return dec.best(st)

        return readout

    # ---- slot-pool state ---------------------------------------------
    def _reset_pool(self) -> None:
        self._slot_bufs: List[np.ndarray] = [
            np.zeros((0,), np.float32) for _ in range(self.n_slots)]
        self._slot_steps = np.zeros((self.n_slots,), np.int64)
        self._stream_state = None
        self._beam = None
        # (n_active, slot bucket b, window bucket w) per fused step —
        # scheduling introspection for tests; bounded so a long-lived
        # streaming engine doesn't accumulate one tuple per 80 ms step
        # forever
        self.step_shapes: deque = deque(maxlen=4096)

    def _ensure_state(self) -> None:
        if self._stream_state is not None:
            return
        # build + place locally, commit both attrs only once everything
        # succeeded: a device_put failure must not leave the pool with a
        # stream state but no beam (commit discipline, RPL008's pattern)
        stream_state = self.program.model.init_batched_stream_state(
            self.program.acoustic, self.n_slots)
        beam = dec.init_batched_state(
            self.n_slots, self.program.dec_cfg.beam_size,
            self.program.lm)
        if self._data_axis is not None:
            # place the pool slot-axis-sharded from the start so the
            # sharded step never reshards it (outputs keep the
            # sharding via out_specs; resets/readouts go through
            # plain jit, which GSPMD partitions: EngineConfig types
            # the mesh's axes Auto)
            from repro.parallel import sharding as shlib
            mesh = self.config.mesh
            stream_state = shlib.place_tree(
                stream_state,
                shlib.asr_state_specs(stream_state, mesh), mesh)
            beam = shlib.place_tree(
                beam, shlib.asr_state_specs(beam, mesh), mesh)
        self._stream_state = stream_state
        self._beam = beam

    def adopt_state(self, old: "AsrEngine") -> None:
        """Take over another engine's in-flight slot-pool state (sample
        buffers, left context, beam, step counts).  Used by the
        deprecated configure-command shims, which must rebuild the
        engine on reconfiguration without losing mid-utterance state."""
        assert old.n_slots == self.n_slots, (old.n_slots, self.n_slots)
        self._slot_bufs = old._slot_bufs
        self._slot_steps = old._slot_steps
        self._stream_state = old._stream_state
        self._beam = old._beam
        self.n_steps = old.n_steps

    def reset_slot(self, slot: int) -> None:
        """Utterance boundary in one slot: clear its buffer, the model's
        carried state (left context, caches), and hypothesis memory;
        other slots are untouched.

        The jitted reset dispatch runs FIRST: it can raise (OOM, a
        poisoned donated buffer), and committing the cleared host-side
        buffers before it would leave the slot half-reset — empty
        buffer, stale beam (RPL008)."""
        if self._stream_state is not None:
            new_stream, new_beam = self._jit_reset(
                self._stream_state, self._beam, slot)
            self._stream_state, self._beam = new_stream, new_beam
        self._slot_bufs[slot] = np.zeros((0,), np.float32)
        self._slot_steps[slot] = 0

    def feed_slot(self, slot: int, samples) -> None:
        """Append raw samples to one slot's stream buffer.  Feeding marks
        decoding intent, so carried state is initialized here — a best
        readout after a partial first chunk sees a fresh beam (score 0,
        no words) rather than the unconfigured -inf sentinel."""
        self._ensure_state()
        self._slot_bufs[slot] = np.concatenate(
            [self._slot_bufs[slot], np.asarray(samples, np.float32)])

    def slot_windows(self, slot: int) -> int:
        """Setup-thread check: whole decoding windows buffered in a slot."""
        return features.frames_producible(
            self._slot_bufs[slot].shape[0],
            self.program.feat_cfg) // self.plan.feat_frames_per_step

    def slot_can_step(self, slot: int) -> bool:
        """A full window of whole frames buffered."""
        return self.slot_windows(slot) >= 1

    @worker_only
    def _step(self) -> bool:
        """One pump round: every slot holding a whole window steps
        exactly once, over at most one gathered dispatch per window
        bucket.  The first dispatch takes the window bucket `w`
        retiring the most buffered windows — w x (slots holding >= w
        windows), largest w on ties (bulk decoding amortizes weight
        reads) — and gathers exactly those slots into the smallest
        covering slot bucket.  Catch-up dispatches then apply the same
        rule to the slots not yet stepped this round (each holds fewer
        than the previous dispatch's w, so every catch-up runs a
        smaller w), until none holding a window is left: an utterance's
        last windows step in the round they appear, so its slot is
        harvested and refilled at once instead of waiting for the other
        slots to reach their own tails.  Live streaming (one window per
        slot) runs one w=1 dispatch.  False (and nothing runs) when no
        slot can produce output — all setup threads returned zero."""
        self._flush_finished_tails()
        avail = np.array([self.slot_windows(s)
                          for s in range(self.n_slots)])
        if not (avail >= 1).any():
            return False
        self._ensure_state()
        catchup = False
        while (avail >= 1).any():
            w = max((b for b in self._buckets if (avail >= b).any()),
                    key=lambda b: (b * int((avail >= b).sum()), b))
            slots = [s for s in range(self.n_slots) if avail[s] >= w]
            avail[slots] = 0
            # `parked`: slots holding a window that no dispatch of the
            # round steps, 0 since the round runs until none is left;
            # trace readers compute the parked share from it
            with TraceAnnotation("engine.step", n=len(slots), w=w,
                                 parked=0, catchup=int(catchup)):
                self._step_isolated(slots, w, catchup)
            catchup = True
        return True

    def _step_isolated(self, slots, w, catchup: bool = False) -> None:
        """Run one gathered step with poison-slot isolation.  On
        failure the step is REPLAYED on bisected halves in probe mode
        (`_step_slots(..., commit=False)`) until the failure pins to
        single slots — probes commit nothing, and assembly is
        non-destructive, so every replay sees the exact same inputs.
        The pinned sessions alone are evicted with a typed
        `SessionFaulted`, then the surviving slots step TOGETHER in one
        committed call: the survivor set pads to the same slot bucket a
        fault-free pump would use, and each batch row depends only on
        its own slot, so survivor trajectories land bitwise identical
        to a fault-free run.  (Committing the probe halves instead
        would step survivors at smaller batch shapes, whose low-order
        float bits differ.)  A failure no probe can reproduce gets one
        committed full-set retry (a transient, not a poison slot); a
        second failure propagates to `_pump_once`'s pool quarantine.
        Slot-level callers (the deprecated command shims) have no
        session to attribute a pinned fault to, so the fault re-raises
        there."""
        try:
            self._step_slots(slots, w, catchup=catchup)
            return
        except Exception as exc:
            if len(slots) == 1:
                sess = self._owner[slots[0]]
                if sess is None:      # slot-level API: nothing to evict
                    raise
                self._fault_session(sess, SessionFaulted(
                    sess.sid, f"decoding step failed: {exc}", cause=exc))
                return
            root = exc
        mid = len(slots) // 2              # the full set just failed:
        bad = (self._probe_step_faults(slots[:mid], w)     # probe halves
               + self._probe_step_faults(slots[mid:], w))
        if not bad:
            # unreproducible under probes: transient — one committed
            # full-set retry, then give up to the pool quarantine
            try:
                self._step_slots(slots, w, catchup=catchup)
            except Exception:
                raise root
            return
        for s, exc in bad:
            sess = self._owner[s]
            if sess is None:          # slot-level API: nothing to evict
                raise exc
            self._fault_session(sess, SessionFaulted(
                sess.sid, f"decoding step failed: {exc}", cause=exc))
        survivors = [s for s in slots if s not in {b for b, _ in bad}]
        if survivors:
            self._step_isolated(survivors, w, catchup)

    def _probe_step_faults(self, slots, w):
        """Bisection probe: non-committing `_step_slots` replays that
        pin a gathered-step failure to its slots.  Returns
        [(slot, exc)] for every slot whose singleton replay fails."""
        try:
            self._step_slots(slots, w, commit=False)
            return []
        except Exception as exc:
            if len(slots) == 1:
                return [(slots[0], exc)]
            mid = len(slots) // 2
            return (self._probe_step_faults(slots[:mid], w)
                    + self._probe_step_faults(slots[mid:], w))

    def _step_slots(self, slots, w, commit: bool = True,
                    catchup: bool = False) -> None:
        """One fused step over exactly `slots` at window count `w`,
        committed ONLY on success: the jitted step is functional (new
        state comes back as fresh arrays), so a raise before the final
        assignments leaves pool state, sample buffers, and metrics
        exactly as they were — the invariant `_step_isolated`'s
        bisection replay depends on.  `commit=False` runs the step and
        discards the result (the isolation probe).  `catchup` marks a
        dispatch after the first of its pump round (metrics only)."""
        with TraceAnnotation("asr.assemble", w=w) as span:
            batch, idx = self._assemble_batch(slots, w)
            b = idx.shape[0]
            span.set_metadata(b=b)
            if self._faults is not None:
                self._faults.check(
                    "asr_step", slots=tuple(slots),
                    sids=tuple(self._owner[s].sid for s in slots
                               if self._owner[s] is not None))
            # transfer-guarded: the batch/idx uploads are the ONLY
            # intended host->device traffic per step; anything implicit
            # (a stray numpy weight, a scalar readback inside dispatch)
            # is a bug
            with no_implicit_transfers():
                if self._input_shardings is not None:
                    batch_d, idx_d = jax.device_put(
                        (batch, idx), self._input_shardings)
                else:
                    batch_d, idx_d = jnp.asarray(batch), jnp.asarray(idx)
        with TraceAnnotation("asr.dispatch"), no_implicit_transfers():
            new_ss, new_beam = self._jit_step(
                self.params, self._prepared, self._tables,
                self._stream_state, self._beam, batch_d, idx_d)
        if not commit:
            return
        self._stream_state, self._beam = new_ss, new_beam
        self._retire(slots, w)
        self._slot_steps[slots] += w
        self.n_steps += 1
        self.step_shapes.append((len(slots), b, w))
        self.metrics.on_step(len(slots), b, catchup)
        for s in slots:
            if self._owner[s] is not None:      # slot-level API has no owner
                self.metrics.on_first_result(self._owner[s])

    def _assemble_batch(self, slots, w):
        """Gather each eligible slot's next `w` buffered windows into a
        bucket-padded (b, w, samples_per_window) batch plus its (b,)
        slot-index vector.  Assembly is NON-destructive — the consumed
        samples are retired by `_retire` only after the fused step
        succeeds, so a faulted step can be replayed on bisected halves
        from unchanged buffers.

        Unsharded / 1D mesh: b is the smallest pow-2 slot bucket
        covering len(slots); padding duplicates row 0 (its repeated
        slot index recomputes an identical update, so the scatter-back
        stays exact).  With a 'data' mesh axis the batch is
        SHARD-ALIGNED: slots group by home shard (slot s lives on shard
        s // slots_per_shard), every shard gets the same local bucket
        `bloc` (smallest covering the largest group) so b = bloc*n_data
        is a multiple of n_data and rows [d*bloc, (d+1)*bloc) land on
        shard d under the step's P('data') in_specs; pad rows are
        zeros with index -1, which the sharded step drops on
        scatter-back (duplicate-padding would be wrong here — a shard
        with no eligible slots has no real row to duplicate)."""
        if self._data_axis is None:
            b = next(x for x in self._slot_buckets if x >= len(slots))
            batch = np.zeros((b, w, self._need), np.float32)
            for j, s in enumerate(slots):
                self._fill_row(batch, j, s, w)
            batch[len(slots):] = batch[0]  # bucket padding: duplicate rows
            idx = np.array(slots + slots[:1] * (b - len(slots)), np.int32)
            return batch, idx
        spshard = self._slots_per_shard
        groups = [[s for s in slots if s // spshard == d]
                  for d in range(self._n_data)]
        bloc = next(x for x in self._slot_buckets
                    if x >= max(len(g) for g in groups))
        batch = np.zeros((bloc * self._n_data, w, self._need), np.float32)
        idx = np.full((bloc * self._n_data,), -1, np.int32)
        for d, group in enumerate(groups):
            for j, s in enumerate(group):
                self._fill_row(batch, d * bloc + j, s, w)
                idx[d * bloc + j] = s
        return batch, idx

    def _fill_row(self, batch, row, slot, w):
        """Extract slot's next w windows into one batch row (window by
        window, exactly as w=1 steps would see them).  The slot buffer
        is NOT consumed here — see `_retire`."""
        for i in range(w):
            off = i * self._spp
            batch[row, i] = self._slot_bufs[slot][off:off + self._need]

    def _retire(self, slots, w):
        """Retire the samples a successful step consumed, keeping the
        MFCC framing overlap buffered.  Separate from `_fill_row` so a
        step that FAULTS retires nothing and the bisection retry sees
        the identical buffers."""
        for s in slots:
            self._slot_bufs[s] = self._slot_bufs[s][w * self._spp:]

    def _flush_finished_tails(self) -> None:
        """Zero-pad the trailing partial window of finished slots so the
        next fused step decodes it.  Without this, `_ready_to_close`
        dropped up to ~step_ms of tail samples (often the end of the
        last word) the moment no FULL window was buffered.  Only slots
        whose buffer holds samples never covered by a decoded frame
        (more than the retained framing overlap) are padded; padding to
        exactly one full window leaves the pure overlap after that step,
        so a flush runs at most once per session and utterances ending
        on a window boundary are untouched (bit-identical to the
        unflushed path).  The window's CTC frames that start in the
        padding are counted (`EngineMetrics.tail_pad_frames`): encoder
        work on zeros, up to a window's worth less one frame."""
        if not self.program.flush_tail:
            return
        per_frame = self._spp // self.plan.acoustic_frames_per_step
        for slot, sess in enumerate(self._owner):
            if sess is None or not sess.finished:
                continue
            n = self._slot_bufs[slot].shape[0]
            if n > self._overlap and not self.slot_can_step(slot):
                self._slot_bufs[slot] = np.concatenate(
                    [self._slot_bufs[slot],
                     np.zeros((self._need - n,), np.float32)])
                self.metrics.on_tail_pad(max(
                    0, self.plan.acoustic_frames_per_step - -(-n // per_frame)))

    def pump(self) -> int:
        """Run decoding steps until no slot has a full window left."""
        n = 0
        while self._step():
            n += 1
        return n

    def slot_best(self, slot: int, final: bool = False) -> dict:
        """Best hypothesis of one slot; final=True commits a pending
        utterance-final word (pure — the stored beam is not advanced)."""
        if self._beam is None:
            return empty_hypothesis()
        fn = self._jit_best_final if final else self._jit_best
        with TraceAnnotation("asr.readout"):
            return dec.materialize_best(fn(self._tables, self._beam, slot))

    # ---- session mechanics -------------------------------------------
    def _push(self, session: Session, chunk) -> None:
        chunk = np.asarray(chunk, np.float32)
        # reject poison input BEFORE buffering: the raise reaches only
        # the pushing caller, nothing was mutated, and the session stays
        # usable for well-formed pushes
        self.program.validate_input(chunk)
        if session.admitted:
            self.feed_slot(session.slot, chunk)
        elif session._pending is None:
            session._pending = chunk
        else:
            session._pending = np.concatenate([session._pending, chunk])
        self._admit()          # fill freed slots; stepping waits for poll

    def _poll(self, session: Session) -> dict:
        self._advance()
        if session.done:
            return copy_result(session.result)
        if session.admitted:
            # slot_best materializes zero-copy views over the jitted
            # readout's device buffers: copy so the caller owns a
            # writable result (and can't see a later step through it)
            res = self.slot_best(session.slot)
            res["steps"] = int(self._slot_steps[session.slot])
            return copy_result(res)
        return self._empty_result()

    def _empty_result(self) -> dict:
        return dict(empty_hypothesis(), steps=0)

    def _admit_to_slot(self, session: Session, slot: int) -> None:
        self.reset_slot(slot)
        if session._pending is not None:
            self.feed_slot(slot, session._pending)

    def _ready_to_close(self, session: Session, slot: int) -> bool:
        if not (session.finished and not self.slot_can_step(slot)):
            return False
        # not closeable while a tail flush is pending: samples beyond
        # the framing overlap still await their zero-padded final step
        return (not self.program.flush_tail
                or self._slot_bufs[slot].shape[0] <= self._overlap)

    def _finalize_slot(self, slot: int) -> dict:
        self._ensure_state()   # finish() before any step still finalizes
        res = self.slot_best(slot, final=True)
        res["steps"] = int(self._slot_steps[slot])
        return copy_result(res)   # stored as session.result: must own it

    def _release_slot(self, slot: int) -> None:
        # eviction mid-utterance: same scrub as an utterance boundary
        self.reset_slot(slot)

    # ---- whole-utterance convenience ---------------------------------
    def serve(self, utterances) -> List[dict]:
        """Continuous batching over whole utterances (audio arrays):
        queued utterances are admitted into freed slots, one vmapped
        step advances every active slot, drained slots are finalized and
        reused.  Results come back in input order."""
        sessions = [self.open() for _ in utterances]
        for sess, audio in zip(sessions, utterances):
            sess.push(audio)       # buffers + admits only — no steps yet,
        for sess in sessions:      # so admitted slots step batched below
            sess.finish()
        assert all(sess.done for sess in sessions), sessions
        return [copy_result(sess.result) for sess in sessions]
