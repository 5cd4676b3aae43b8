"""Labelled texts made from ``--seed``: the one generator every text cell
reads, as ``tables.py`` is for the booster's cells.

A traffic file's ``texts`` block gives ``generator`` and its parameters.
``reviews_like`` is a review or ticket corpus without the network: ``count``
texts, the labels of ``classes`` classes balanced, word counts log-normal
(``length``: ``median``, ``sigma``, clipped to ``min``..``max``), words drawn
with Zipf's frequencies (``zipf``) from a list of ``words.count``
pseudo-words that ``words.seed`` fixes for every run, so that a tokenizer's
hash buckets collide as they do on real text. A share ``topic_share`` of
every text's words comes from its label's own ``topic_words`` words, so the
labels can be learned. The texts are drawn from ``base_seed`` and ``--seed``;
a dense training step computes every position, padding included, so the
work is the same for every seed. The same seed gives the same texts.
"""

from __future__ import annotations

import numpy as np

_ONSETS = ("b c d f g h j k l m n p r s t v w z ch sh th st tr pl gr br "
           "cl fr sp").split()
_VOWELS = "a e i o u ai ea ou io".split()
_CODAS = ["", "", "n", "r", "s", "t", "l", "m", "ng", "st", "ck"]


def word_list(count: int, seed: int) -> np.ndarray:
    """``count`` distinct lower-case pseudo-words of one to four syllables,
    the short ones first, as frequent words are."""
    rng = np.random.default_rng(int(seed))
    seen, words = set(), []
    while len(words) < count:
        syllables = 1 + min(3, len(words) * 4 // count + rng.integers(0, 2))
        w = "".join(_ONSETS[rng.integers(len(_ONSETS))]
                    + _VOWELS[rng.integers(len(_VOWELS))]
                    + _CODAS[rng.integers(len(_CODAS))]
                    for _ in range(syllables))
        if w not in seen:
            seen.add(w)
            words.append(w)
    return np.array(words, dtype=object)


def _reviews_like(spec: dict, seed: int):
    count, classes = int(spec["count"]), int(spec["classes"])
    words = word_list(int(spec["words"]["count"]), int(spec["words"]["seed"]))
    rng = np.random.default_rng([int(spec["base_seed"]), int(seed)])
    labels = rng.permutation(np.arange(count) % classes)
    ln = spec["length"]
    lengths = np.clip(np.rint(np.exp(rng.normal(
        np.log(float(ln["median"])), float(ln["sigma"]), count))),
        int(ln["min"]), int(ln["max"])).astype(np.int64)
    rank = np.arange(len(words), dtype=np.float64)
    p = 1.0 / (rank + 2.7) ** float(spec["zipf"])
    total = int(lengths.sum())
    ids = rng.choice(len(words), size=total, p=p / p.sum())
    # every label's topic: topic_words words of the list's middle ranks
    topic = int(spec["topic_words"])
    topics = np.random.default_rng(int(spec["words"]["seed"]) + 1).choice(
        np.arange(len(words) // 50, len(words) // 2), size=(classes, topic),
        replace=False)
    owner = np.repeat(labels, lengths)
    from_topic = rng.random(total) < float(spec["topic_share"])
    ids[from_topic] = topics[owner[from_topic],
                             rng.integers(0, topic, int(from_topic.sum()))]
    ends = np.cumsum(lengths)
    texts = [" ".join(words[ids[e - n:e]]) for e, n in zip(ends, lengths)]
    return texts, labels.astype(np.int64)


GENERATORS = {"reviews_like": _reviews_like}


def make(spec: dict, seed: int):
    """(list of texts, int64 labels)."""
    return GENERATORS[spec["generator"]](spec, int(seed))
