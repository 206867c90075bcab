"""Synthetic report text for the LM swarm cells: token sequences from a
class-conditioned Zipf-Markov source over the whole vocabulary, site
shards, and the round blocks a swarm trainer feeds, all from a seed.

A report belongs to one of ``len(class_probs)`` types (drawn with those
probabilities), and each type orders the vocabulary by its own random
permutation. Token t + 1 is the type's rank ``z`` (a fresh topic word)
with probability ``restart``, else the rank of token t moved on by ``z``
(a continuation), ``z - 1`` drawn from a Zipf law of exponent
``zipf``: heavy-tailed word frequencies, runs that depend on the word
before, and a vocabulary of each type's own. Shards follow the paper's
10/30/30/30 split of ``n_train`` reports; each site keeps its first
``val_seqs`` reports to validate on and trains on the rest.
"""
from __future__ import annotations

import numpy as np

from swarmbench.traffic import paper_splits, seed32


def reports(rng, n: int, length: int, vocab: int, data: dict):
    """(tokens [n, length] int32, types [n])."""
    probs = np.asarray(data["class_probs"], float)
    types = rng.choice(len(probs), size=n, p=probs / probs.sum())
    perms = np.stack([rng.permutation(vocab) for _ in probs])
    z = np.minimum(rng.zipf(data["zipf"], (n, length)) - 1, vocab - 1)
    fresh = rng.random((n, length)) < data["restart"]
    fresh[:, 0] = True
    rank = np.empty((n, length), np.int64)
    rank[:, 0] = z[:, 0]
    for t in range(1, length):
        rank[:, t] = np.where(fresh[:, t], z[:, t],
                              (rank[:, t - 1] + z[:, t]) % vocab)
    return perms[types[:, None], rank].astype(np.int32), types


def build(config: dict, traffic: dict, seed: int):
    """The cell's inputs from ``seed``: a pool of ``traffic["pool_rounds"]``
    round blocks ``(tokens, labels)``, each ``[T, N, B, S]``, each site's
    validation reports ``(tokens, labels)`` ``[N, V, S]``, and each
    site's shard size (the fedavg weights)."""
    data, s = config["data"], seed32(seed)
    rng = np.random.default_rng(s)
    length = traffic["seq_len"] + 1                   # inputs and labels
    sizes = paper_splits(data["n_train"], data["fractions"])
    toks, _ = reports(rng, data["n_train"], length, config["vocab_size"],
                      data)
    cuts = np.cumsum([0] + sizes)
    shards = [toks[a:b] for a, b in zip(cuts[:-1], cuts[1:])]
    v = traffic["val_seqs"]
    val = np.stack([sh[:v] for sh in shards])
    t, b, rounds = traffic["sync_every"], traffic["batch"], \
        traffic["pool_rounds"]
    # each site walks its own training reports in a shuffled order
    picks = [rng.permutation(np.resize(np.arange(v, len(sh)),
                                       rounds * t * b))
             for sh in shards]
    seqs = np.stack([sh[p] for sh, p in zip(shards, picks)], axis=1)
    seqs = seqs.reshape(rounds, t, b, len(shards), length).transpose(
        0, 1, 3, 2, 4)                                 # [R, T, N, B, S+1]
    pool = [(blk[..., :-1], blk[..., 1:]) for blk in seqs]
    return {"pool": pool, "val": (val[..., :-1], val[..., 1:]),
            "data_sizes": sizes}
