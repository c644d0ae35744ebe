"""The streaming FastConformer + CTC recognizer family: data from the seed,
the engine under test, its warm-up, and the comparison with the plain
reference.

The configuration file (configs/<name>.json) gives the sizes; this module
turns them into the program's own objects (`repro.serving.AsrEngine` over
a `ConformerConfig`).  Weights, lexicon, LM counts and audio are made
here from the seed, so that the reference (fastconformer_ctc_reference.py)
takes nothing the program made.  The lexicon, LM and comparison are the
TDS family's (tds_ctc.py).
"""
from __future__ import annotations

import time

import numpy as np

import fastconformer_ctc_reference as ref
from repro.configs.fastconformer import ConformerConfig
from tds_ctc import best_of, compare, make_counts, make_words, pad_trie  # noqa: F401


class System:
    """What one seed makes: weights (on the device), words, LM counts."""

    def __init__(self, cfg: dict, seed: int):
        import jax

        self.cfg = cfg
        sizes, dec, lex = cfg["model"], cfg["decoder"], cfg["lexicon"]
        rng = np.random.default_rng(seed)
        self.words = make_words(rng, lex["n_words"], dec["max_children"],
                                sizes["vocab"])
        self.counts = make_counts(rng, lex["n_words"])
        self.params = jax.jit(lambda k: ref.init_params(k, sizes))(
            jax.random.PRNGKey(seed))
        jax.block_until_ready(self.params)


def conformer_config(sizes: dict):
    """The program's `ConformerConfig` for a configuration's sizes."""
    chunk, n_left = ref.chunk_of(sizes)
    return ConformerConfig(
        n_mels=sizes["n_mels"], sub_layers=sizes["sub_layers"],
        sub_channels=sizes["sub_channels"], d_model=sizes["d_model"],
        n_layers=sizes["n_layers"], n_heads=sizes["n_heads"],
        ff_expansion=sizes["ff_expansion"],
        conv_kernel=sizes["conv_kernel"], chunk=chunk, left_chunks=n_left,
        vocab_size=sizes["vocab"])


def build_engine(system: System):
    """The program's `AsrEngine` over this system, as the configuration
    states it (f32 weights, its kernel policy, slot pool, window fusion)."""
    from repro.configs.tds_asr import DecoderConfig
    from repro.core import lexicon as lx
    from repro.kernels.policy import KernelPolicy
    from repro.serving import AsrEngine, AsrProgram, EngineConfig

    cfg = system.cfg
    dec = cfg["decoder"]
    lex = pad_trie(lx.build_lexicon(
        {f"w{i}": t for i, t in enumerate(system.words)},
        max_children=dec["max_children"]), cfg["lexicon"]["trie_nodes"])
    lm = lx.bigram_from_counts(system.counts)
    program = AsrProgram(conformer_config(cfg["model"]), lex, lm,
                         dec_cfg=DecoderConfig(**dec),
                         max_windows_per_step=cfg["max_windows_per_step"])
    return AsrEngine(EngineConfig(program, n_slots=cfg["n_slots"],
                                  kernels=KernelPolicy(cfg["kernels"])),
                     system.params)


def warm_up(engine, log) -> None:
    """Run each step entry (slot bucket, window bucket) once, with the
    poll and final readouts and the slot reset: b sessions, each pushed
    exactly w windows, step together at (b, w) and leave only the
    framing overlap, so nothing else runs."""
    fc = engine.program.feat_cfg
    samples = engine.plan.samples_per_step
    overlap = fc.frame_len - fc.frame_shift
    for b, w in engine.step_entries():
        t = time.perf_counter()
        audio = np.zeros(overlap + w * samples, np.float32)
        sessions = [engine.open() for _ in range(b)]
        for sess in sessions:
            sess.push(audio)
        sessions[0].poll()
        for sess in sessions:
            sess.finish()
        log(f"warm-up: {b} slots, w={w} {time.perf_counter() - t:.3f}s")


class Reference:
    """The plain reference over one system: the encoder in `dtype` and
    `precision`, then the search in float64."""

    def __init__(self, system: System, dtype: str = "float32",
                 precision: str = "highest"):
        self.system = system
        self.sizes = system.cfg["model"]
        self.dec = system.cfg["decoder"]
        self.dtype, self.precision = dtype, precision
        self.trie = ref.Trie(system.words, self.dec["max_children"])
        self.lm = ref.bigram_table(system.counts)
        self._fwd = None

    def log_probs(self, audios: list, n_windows: int, rows: int = 0) -> list:
        """Log-probs of each utterance, all padded to `n_windows` windows
        and the batch to `rows` rows (one compiled shape; the encoder is
        causal, chunk by chunk, so padding at the end changes no earlier
        frame)."""
        import jax

        if self._fwd is None:
            self._fwd = jax.jit(lambda p, x: ref.forward(
                p, self.sizes, x, self.dtype, self.precision))
        wins = [ref.windows_of(a, self.sizes) for a in audios]
        assert all(len(w) <= n_windows for w in wins), "window cap"
        batch = np.zeros((max(rows, len(wins)), n_windows,
                          ref.window_samples(self.sizes)), np.float32)
        for i, w in enumerate(wins):
            batch[i, :len(w)] = w
        lp = np.asarray(self._fwd(self.system.params, batch))
        chunk = ref.chunk_of(self.sizes)[0]
        return [lp[i, :len(w) * chunk] for i, w in enumerate(wins)]

    def decode(self, logp) -> ref.Beam:
        return ref.search(logp, self.trie, self.lm, self.dec)
