"""Lexicon trie + n-gram language model as dense padded arrays.

ASRPU traverses graph structures (lexicon tree, n-gram LM) with random
access through an LRU cache (paper §3.6).  The TPU-idiomatic equivalent is
dense padded arrays traversed with gathers (DESIGN.md §2): each trie node
stores up to `max_children` (child_id, token) pairs; word-final nodes carry
a word id for the LM.

The n-gram LM here is a bigram table (dense (n_words+1, n_words) log-prob
matrix; row n_words = sentence start).  Production n-gram models would use
the same interface over hashed arrays; the decoder only calls `lm_score`
and `lm_advance`.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np


# Lexicon and LM are pytrees so that jitted programs take their tables
# as arguments: closed over, device arrays become program constants,
# which a TPU must first copy back to the host to embed.
@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class Lexicon:
    """Padded trie over acoustic tokens."""
    children: jax.Array      # (n_nodes, C) int32 child node id, -1 = pad
    child_token: jax.Array   # (n_nodes, C) int32 acoustic token on the edge
    word_id: jax.Array       # (n_nodes,) int32 word id if word-final else -1
    n_nodes: int = field(metadata=dict(static=True))
    max_children: int = field(metadata=dict(static=True))

    @property
    def root(self) -> int:
        return 0


def build_lexicon(words: Dict[str, Sequence[int]], max_children: int) -> Lexicon:
    """words: word -> token-id sequence. Word ids = insertion order."""
    children: List[Dict[int, int]] = [{}]
    word_id: List[int] = [-1]
    for wid, (_word, toks) in enumerate(words.items()):
        node = 0
        for t in toks:
            nxt = children[node].get(t)
            if nxt is None:
                nxt = len(children)
                children[node][t] = nxt
                children.append({})
                word_id.append(-1)
            node = nxt
        word_id[node] = wid
    n = len(children)
    ch = np.full((n, max_children), -1, np.int32)
    ct = np.full((n, max_children), -1, np.int32)
    for i, cs in enumerate(children):
        assert len(cs) <= max_children, f"fanout {len(cs)} > {max_children}"
        for j, (t, c) in enumerate(sorted(cs.items())):
            ch[i, j] = c
            ct[i, j] = t
    return Lexicon(jnp.asarray(ch), jnp.asarray(ct), jnp.asarray(word_id),
                   n, max_children)


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class BigramLM:
    """log P(w | prev). State = prev word id; start state = n_words."""
    table: jax.Array         # (n_words + 1, n_words) f32 log-probs
    n_words: int = field(metadata=dict(static=True))

    @property
    def start_state(self) -> int:
        return self.n_words

    def score(self, state: jax.Array, word: jax.Array) -> jax.Array:
        return self.table[state, word]

    def advance(self, state: jax.Array, word: jax.Array) -> jax.Array:
        del state
        return word


def uniform_bigram(n_words: int) -> BigramLM:
    t = jnp.full((n_words + 1, n_words), -np.log(n_words), jnp.float32)
    return BigramLM(t, n_words)


def bigram_from_counts(counts: np.ndarray, alpha: float = 0.5) -> BigramLM:
    """counts: (n_words+1, n_words) raw bigram counts (last row = <s>)."""
    c = counts.astype(np.float64) + alpha
    t = np.log(c / c.sum(axis=1, keepdims=True)).astype(np.float32)
    return BigramLM(jnp.asarray(t), counts.shape[1])
