"""The TDS + CTC recognizer family: data from the seed, the engine under
test, its warm-up, and the comparison with the plain reference.

The configuration file (configs/<name>.json) gives the sizes; this module
turns them into the program's own objects (`repro.serving.AsrEngine`).
Weights, lexicon, LM counts and audio are made here from the seed, so
that the reference (tds_ctc_reference.py) takes nothing the program made.
"""
from __future__ import annotations

import time

import numpy as np

import tds_ctc_reference as ref


# ---- data from the seed -----------------------------------------------------

def make_words(rng, n_words: int, fanout: int, vocab: int) -> list:
    """Token sequences of `n_words` words, 2 to 6 tokens each, grown as a
    trie whose nodes have at most `fanout` children (the generator of
    `launch.serve.paper_asr_system`)."""
    kids = [{}]
    is_word = [False]
    words = []
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
            words.append(toks)
    return words


def make_counts(rng, n_words: int) -> np.ndarray:
    """(n_words + 1, n_words) Zipf-shaped bigram counts; last row = <s>."""
    zipf = 1.0 / np.arange(1, n_words + 1)
    return rng.poisson(n_words * zipf / zipf.sum() * 8.0,
                       size=(n_words + 1, n_words)).astype(np.int32)


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


# ---- the program ------------------------------------------------------------

def build_engine(system: System):
    """The program's `AsrEngine` over this system, as the configuration
    states it (f32 weights, its kernel policy, slot pool, window fusion)."""
    from repro.configs.tds_asr import DecoderConfig, TDSConfig, TDSStage
    from repro.core import lexicon as lx
    from repro.kernels.policy import KernelPolicy
    from repro.serving import AsrEngine, AsrProgram, EngineConfig

    cfg = system.cfg
    sizes, dec = cfg["model"], cfg["decoder"]
    tds_cfg = TDSConfig(
        n_mfcc=sizes["n_mfcc"],
        stages=tuple(TDSStage(n_blocks=n, channels=c, feat=sizes["feat"],
                              kernel=k, subsample=s)
                     for n, c, k, s in sizes["stages"]),
        sub_kernel=sizes["sub_kernel"], vocab_size=sizes["vocab"])
    lex = pad_trie(lx.build_lexicon(
        {f"w{i}": t for i, t in enumerate(system.words)},
        max_children=dec["max_children"]), cfg["lexicon"]["trie_nodes"])
    lm = lx.bigram_from_counts(system.counts)
    program = AsrProgram(tds_cfg, lex, lm, dec_cfg=DecoderConfig(**dec),
                         max_windows_per_step=cfg["max_windows_per_step"])
    return AsrEngine(EngineConfig(program, n_slots=cfg["n_slots"],
                                  kernels=KernelPolicy(cfg["kernels"])),
                     system.params)


def pad_trie(lex, rows: int):
    """The trie's tables padded with unreachable nodes to `rows` rows, so
    that every seed's trie has the same shape and the step programs
    compiled for one seed serve every other (the node count is a static
    part of the program's `Lexicon`)."""
    import jax.numpy as jnp
    from repro.core.lexicon import Lexicon

    pad = rows - lex.n_nodes
    if pad < 0:
        raise SystemExit(f"trie of {lex.n_nodes} nodes exceeds the "
                         f"configured {rows} rows")
    return Lexicon(jnp.pad(lex.children, ((0, pad), (0, 0)),
                           constant_values=-1),
                   jnp.pad(lex.child_token, ((0, pad), (0, 0)),
                           constant_values=-1),
                   jnp.pad(lex.word_id, (0, pad), constant_values=-1),
                   rows, lex.max_children)


def warm_up(engine, log) -> None:
    """Step every count of live slots, 1 to `n_slots`, at every window
    bucket, and run the poll and final readouts and the slot reset, so
    that no step entry (slot bucket, window bucket) compiles later,
    whatever buckets the engine groups the slot counts into."""
    samples = engine.plan.samples_per_step
    need = ref.WINDOW_SAMPLES
    for w in engine.program.step_buckets():
        audio = np.zeros(need + (w - 1) * samples, np.float32)
        for b in range(engine.n_slots, 0, -1):
            t = time.perf_counter()
            sessions = [engine.open() for _ in range(b)]
            for sess in sessions:
                sess.push(audio)
            sessions[0].poll()
            for sess in sessions:
                sess.finish()
            log(f"warm-up: {b} slots, w={w} "
                f"{time.perf_counter() - t:.3f}s")


# ---- the comparison with the reference --------------------------------------

class Reference:
    """The plain reference over one system: the acoustic model in `dtype`
    and `precision`, then the search in float64."""

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
        and the batch to `rows` rows (one compiled shape; the acoustic
        model is causal, so padding at the end changes no earlier
        frame)."""
        import jax

        if self._fwd is None:
            self._fwd = jax.jit(lambda p, x: ref.forward(
                p, self.sizes, x, self.dtype, self.precision))
        wins = [ref.windows_of(a) for a in audios]
        assert all(len(w) <= n_windows for w in wins), "window cap"
        batch = np.zeros((max(rows, len(wins)), n_windows,
                          ref.WINDOW_SAMPLES), np.float32)
        for i, w in enumerate(wins):
            batch[i, :len(w)] = w
        lp = np.asarray(self._fwd(self.system.params, batch))
        return [lp[i, :len(w)] for i, w in enumerate(wins)]

    def decode(self, logp) -> ref.Beam:
        return ref.search(logp, self.trie, self.lm, self.dec)


def compare(served: list, beams: list) -> dict:
    """The numbers a run may compare, over served results and the
    reference's final beams for the same audio.  Per result, the gap is
    |served score - the reference's best score| over max(1, |the
    reference's best score|):

    * `score_gap`: the widest gap;
    * `median_gap`: the median gap;
    * `rescore_gap`: the widest |served score - the reference's score of
      the served hypothesis (its tokens and words)|, on the same scale;
      a served hypothesis that is not in the reference's final beam
      reads 1e9.
    """
    gaps, rescore = [], []
    for res, beam in zip(served, beams):
        best = ref.best(beam)
        scale = max(1.0, abs(best["score"]))
        got = float(res["score"])
        gaps.append(abs(got - best["score"]) / scale)
        key = (ref.served_form(res["tokens"], ref.MAX_TOKENS),
               ref.served_form(res["words"], ref.MAX_WORDS))
        mine = ref.hypotheses(beam).get(key)
        rescore.append(1e9 if mine is None else abs(got - mine) / scale)
    return {"score_gap": max(gaps, default=0.0),
            "median_gap": float(np.median(gaps)) if gaps else 0.0,
            "rescore_gap": max(rescore, default=0.0)}


def best_of(beam: ref.Beam) -> dict:
    """A reference beam's best hypothesis as a served result carries it."""
    b = ref.best(beam)
    return {"tokens": list(b["tokens"]), "words": list(b["words"]),
            "score": b["score"]}
