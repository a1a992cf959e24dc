"""Traffic generators, driven by the parameters of ``bench/traffic/*.json``.

* ``chat_sizes`` — prompt and output lengths of a chat mix.  Each block
  of ``strata`` requests holds the midpoints of ``strata`` equal-
  probability strata of the clipped lognormal, shuffled by the traffic's
  own ``order_seed`` and not by the run's seed: every seed serves the
  same sizes in the same order, and any stretch of consecutive requests
  already follows the distribution, so the metrics measured over a
  window do not swing with the seed.  The seed draws the token ids.
* ``bigram_batches`` — a copy of the program's ``BigramStream`` (a seeded
  bigram language with a fixed transition table), generated on the
  device from the run's seed.
"""

from __future__ import annotations

from statistics import NormalDist
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np


def _strata(spec: dict, k: int) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf((i + 0.5) / k) for i in range(k)])
    x = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def chat_sizes(traffic: dict) -> Iterator[tuple[int, int]]:
    """Endless (prompt_len, output_len) pairs, the same for every seed."""
    k = traffic["strata"]
    prompts = _strata(traffic["prompt"], k)
    outputs = _strata(traffic["output"], k)
    rng = np.random.default_rng([traffic["order_seed"], 0x51])
    while True:
        for p, o in zip(rng.permutation(prompts), rng.permutation(outputs)):
            yield int(p), int(o)


def chat_requests(traffic: dict, seed: int, vocab: int
                  ) -> Iterator[tuple[np.ndarray, int]]:
    """Endless (prompt tokens, output length) pairs: the sizes of
    :func:`chat_sizes`, token ids drawn from ``seed``."""
    rng = np.random.default_rng([seed, 0x7E])
    for p, o in chat_sizes(traffic):
        yield rng.integers(0, vocab, p).astype(np.int32), o


# ---------------------------------------------------------------------------
# bigram language (copy of the program's BigramStream, on the device)
# ---------------------------------------------------------------------------

def bigram_table(vocab: int, branching: int, table_seed: int):
    """Successors [V, branching] and their cumulative probabilities."""
    root = np.random.default_rng(table_seed)
    succ = root.integers(0, vocab, (vocab, branching)).astype(np.int32)
    probs = root.dirichlet(np.ones(branching), size=vocab).astype(np.float32)
    return jnp.asarray(succ), jnp.asarray(np.cumsum(probs, axis=1))


def _rows(key, succ, cum, rows: int, length: int):
    k0, k1 = jax.random.split(key)
    first = jax.random.randint(k0, (rows,), 0, succ.shape[0], jnp.int32)

    def body(tok, k):
        u = jax.random.uniform(k, (rows, 1))
        choice = jnp.argmax(u < cum[tok], axis=1)
        nxt = succ[tok, choice]
        return nxt, nxt

    _, rest = jax.lax.scan(body, first, jax.random.split(k1, length - 1))
    return jnp.concatenate([first[:, None], rest.T], axis=1)


def bigram_batches(key, traffic: dict, vocab: int, rows: int, n: int,
                   sharding=None) -> tuple:
    """``n`` distinct batches of ``rows`` x (seq_len + 1) tokens, made in
    one jitted call from ``key``."""
    d = traffic["data"]
    succ, cum = bigram_table(vocab, d["branching"], d["table_seed"])
    length = traffic["seq_len"] + 1

    def make(key, succ, cum):
        return tuple(_rows(jax.random.fold_in(key, i), succ, cum, rows,
                           length) for i in range(n))

    out = None if sharding is None else (sharding,) * n
    return jax.jit(make, out_shardings=out)(key, succ, cum)

