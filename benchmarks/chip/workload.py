"""Cells, configurations and traffic, found by name under this directory.

A cell's traffic file, ``traffic/<cell>.json``, names its configuration and
holds the parameters of one general generator:

  * ``slots``, ``max_len``, ``block_k``: the serving engine's shape;
  * ``prompt_block``: ``[[length, count], ...]``; every block of that many
    consecutive requests holds exactly these prompt lengths, interleaved so
    that each stretch of the queue holds each length near its share;
  * ``output``: ``[lo, hi]``; every ``OUTPUT_CYCLE`` consecutive requests
    (or a block, where that is longer) take the midpoints of as many equal
    strata of the range as their output lengths, in a fixed order;
  * ``requests``: the queue's length, more than any window drains;
  * ``sample_tokens``: how many served tokens of finished requests the
    correctness check compares against the reference at least, where the
    window finished that many (the longest request first, then others
    drawn from the seed); ``min_compared``: the fewest a run may compare;
    ``gap_limit``: the limit of the widest gap;
  * ``trace_seconds``: how much of the window a traced run profiles;
  * ``token_skew`` (optional): ``{"topics": T, "ids_per_topic": N,
    "zipf": s}``.  Each of ``T`` topics is a subset of ``N`` ids of the
    vocabulary, drawn from the seed in an order that ranks them; each
    request draws one topic, and its prompt's ids follow a Zipf law of
    exponent ``s`` over that topic's ``N`` ranks.  Skewed ids route
    unevenly over experts, as real topics do; without the key ids are
    uniform over the vocabulary.

The requests that first fill the slots take their output lengths the same
way over ``[1, hi]``, so slots free at a steady rate from the start, and
their prompt lengths in the block's shares (largest remainders).

Every seed gets the same requests' sizes: the window's work, and so its
times, must not change with the seed.  The seed deals the filling requests
to the slots in an order of its own, and draws every token id (uniform over
the configuration's vocabulary, or by ``token_skew``) and the weights.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
from typing import Dict, List

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
OUTPUT_CYCLE = 16


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent numpy streams from any non-negative whole-number seed."""
    return np.random.default_rng([stream, seed])


def load_json(kind: str, name: str) -> Dict:
    path = HERE / kind / f"{name}.json"
    if not path.is_file():
        raise SystemExit(f"no {kind[:-1] if kind.endswith('s') else kind} "
                         f"named {name!r} ({path})")
    return json.loads(path.read_text())


@dataclasses.dataclass
class Queue:
    prompts: List[np.ndarray]
    gens: List[int]

    @property
    def prompt_lens(self) -> List[int]:
        return sorted({len(p) for p in self.prompts})


def _interleave(counts) -> List[int]:
    """Lengths with these counts, the i-th of ``c`` copies at (i + 1/2)/c,
    so that every stretch holds each length near its share."""
    return [n for _, n in sorted(((i + 0.5) / c, n) for n, c in counts
                                 for i in range(c))]


def _spread(lo: int, hi: int, n: int) -> List[int]:
    """n lengths over [lo, hi], the midpoint of each of n equal strata, in
    an order fixed for every seed so that short and long outputs fall on
    every prompt length."""
    span = hi - lo + 1
    mid = lo + np.minimum(((np.arange(n) + 0.5) / n * span).astype(np.int64),
                          span - 1)
    return mid[np.random.default_rng(0).permutation(n)].tolist()


def _fill_counts(block, slots: int):
    """``slots`` prompt lengths in the block's shares, largest remainders
    first (ties to the earlier length)."""
    total = sum(c for _, c in block)
    raw = [slots * c / total for _, c in block]
    counts = [int(r) for r in raw]
    ranked = sorted(range(len(block)), key=lambda j: counts[j] - raw[j])
    for j in ranked[:slots - sum(counts)]:
        counts[j] += 1
    return [(n, k) for (n, _), k in zip(block, counts) if k]


def zipf_shares(n: int, s: float) -> np.ndarray:
    """The Zipf law of exponent ``s`` over ranks 1..n."""
    w = np.arange(1, n + 1, dtype=np.float64) ** -s
    return w / w.sum()


def _skewed_ids(skew: Dict, vocab: int, lens: List[int],
                rng: np.random.Generator) -> np.ndarray:
    """Prompt ids by topic: ``topics`` ranked subsets of ``ids_per_topic``
    ids, one topic a request, ranks by a Zipf law of exponent ``zipf``."""
    keys = {"topics", "ids_per_topic", "zipf"}
    if set(skew) != keys:
        raise ValueError(f"token_skew takes exactly the keys {sorted(keys)}, "
                         f"not {sorted(skew)}")
    topics, per, s = skew["topics"], skew["ids_per_topic"], skew["zipf"]
    if not (isinstance(topics, int) and topics >= 1):
        raise ValueError(f"token_skew topics must be a whole number >= 1, "
                         f"not {topics!r}")
    if not (isinstance(per, int) and 1 <= per <= vocab):
        raise ValueError(f"token_skew ids_per_topic must be a whole number "
                         f"in [1, {vocab}], not {per!r}")
    if not (isinstance(s, (int, float)) and s > 0):
        raise ValueError(f"token_skew zipf must be > 0, not {s!r}")
    ranked = np.stack([rng.choice(vocab, per, replace=False)
                       for _ in range(topics)]).astype(np.int32)
    topic = np.repeat(rng.integers(0, topics, len(lens)), lens)
    rank = rng.choice(per, size=sum(lens), p=zipf_shares(per, s))
    return ranked[topic, rank]


def make_queue(traffic: Dict, vocab: int, seed: int) -> Queue:
    block = [(int(n), int(c)) for n, c in traffic["prompt_block"]]
    lo, hi = traffic["output"]
    slots, total = traffic["slots"], traffic["requests"]
    lens = _interleave(_fill_counts(block, slots))
    gens = _spread(1, hi, slots)
    body_lens = _interleave(block)
    body_gens = _spread(lo, hi, max(len(body_lens), OUTPUT_CYCLE))
    while len(lens) < total:
        lens += body_lens
    while len(gens) < total:
        gens += body_gens
    lens, gens = lens[:total], gens[:total]
    rng = rng_for(seed, 1)
    deal = rng.permutation(slots)
    lens[:slots] = [lens[i] for i in deal]
    gens[:slots] = [gens[i] for i in deal]
    if max(lens) + hi + 1 > traffic["max_len"]:
        raise ValueError("the longest request overruns max_len")
    if "token_skew" in traffic:
        ids = _skewed_ids(traffic["token_skew"], vocab, lens, rng)
    else:
        ids = rng.integers(0, vocab, sum(lens), dtype=np.int32)
    prompts = np.split(ids, np.cumsum(lens)[:-1])
    return Queue(prompts=prompts, gens=[int(g) for g in gens])
