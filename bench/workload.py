"""The one generator of inputs: serving requests, from a traffic file's
parameters and the run's seed.

Every seed gets the same sizes in the same order: the lengths are
stratified draws from the traffic file's distributions under its fixed
``size_seed``, which also orders them; the run's seed picks the token
ids. So two seeds do the same amount of work,
and their spread is the system's, not the generator's.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List

import numpy as np


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.Generator(np.random.Philox(key=[seed, stream]))


def stratified(dist: Dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` integer lengths, one from each of ``n`` equal-probability
    strata of ``dist`` ({"dist": "uniform" | "log_uniform", "lo", "hi"},
    both ends included), in stratum order."""
    lo, hi = int(dist["lo"]), int(dist["hi"])
    u = (np.arange(n) + rng.random(n)) / n
    if dist["dist"] == "uniform":
        x = lo + u * (hi - lo + 1)
    elif dist["dist"] == "log_uniform":
        x = np.exp(math.log(lo) + u * (math.log(hi + 1) - math.log(lo)))
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


@dataclasses.dataclass(frozen=True)
class Req:
    uid: str
    prompt: List[int]
    max_new: int


def serve_requests(traffic: Dict, vocab: int, seed: int) -> List[Req]:
    """The run's requests in the order the clients send them.

    The first ``clients`` requests start the closed loop mid-stream, as if
    each client had been decoding when the window opened: of its drawn
    output, the fixed fraction (k + 1/2) / clients is left to decode (k a
    permutation of the clients), and the part already decoded joins its
    prompt. So the loop starts near its steady mix of contexts, the long
    ones included, instead of with every client at its prompt."""
    n = int(traffic["requests"])
    srng = _rng(int(traffic["size_seed"]), 0)
    prompts = stratified(traffic["prompt"], n, srng)
    outputs = stratified(traffic["output"], n, srng)[srng.permutation(n)]
    order = srng.permutation(n)
    prompts, outputs = prompts[order], outputs[order]
    clients = int(traffic["clients"])
    frac = (srng.permutation(clients) + 0.5) / clients
    left = np.maximum(1, np.ceil(outputs[:clients] * frac)).astype(np.int64)
    prompts[:clients] += outputs[:clients] - left
    outputs[:clients] = left
    ids = _rng(seed, 2)
    return [Req(uid=f"r{i}", prompt=ids.integers(0, vocab, int(p)).tolist(),
                max_new=int(o))
            for i, (p, o) in enumerate(zip(prompts, outputs))]
