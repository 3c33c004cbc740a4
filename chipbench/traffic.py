"""The one traffic generator. A traffic file (``traffic/<name>.json``)
gives its ``kind`` and parameters; the seed picks the order and the
prompt tokens.

Every seed gets the same set of (prompt, output) sizes and inter-arrival
gaps, taken at evenly spaced quantiles of the stated distributions and
paired and ordered in one fixed way, so a run's work does not depend on
its seed. The seed picks the prompt tokens, and with them every
generated token. So runs with different seeds do the same amount of work,
and differ only in which request comes when and in the token ids.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def lognormal_sizes(n: int, spec: dict) -> np.ndarray:
    """``n`` sizes at the quantiles (i + 1/2)/n of a lognormal with the
    given ``median`` and ``sigma``, clipped to [``min``, ``max``]."""
    nd = NormalDist()
    q = [nd.inv_cdf((i + 0.5) / n) for i in range(n)]
    x = spec["median"] * np.exp(spec["sigma"] * np.asarray(q))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def exponential_gaps(n: int) -> np.ndarray:
    """``n`` unit-mean gaps at the quantiles (i + 1/2)/n."""
    return np.asarray([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])


def open_loop(traffic: dict, *, seed: int, seconds: float,
              vocab: int) -> dict:
    """Requests for one run of an open-loop mix.

    ``warm``: requests submitted together at set-up, to bring the server
    to steady occupancy before the window opens. ``window``: requests
    due at offsets from the window's opening, ``rate_rps`` x ``seconds``
    of them with Poisson-quantile gaps spanning the window. Each request
    is ``(due_s, prompt, max_new_tokens)``; ``due_s`` is None for warm
    requests."""
    rng = np.random.default_rng(seed)
    n_win = max(1, int(round(traffic["rate_rps"] * seconds)))
    n_warm = int(traffic.get("warm_requests", 0))

    order_rng = np.random.default_rng(0)

    def sizes(n):
        # a fixed pairing of prompt and output quantiles
        p = lognormal_sizes(n, traffic["prompt"])
        o = lognormal_sizes(n, traffic["output"])
        o = o[np.random.default_rng(0).permutation(n)]
        order = order_rng.permutation(n)
        return p[order], o[order]

    gaps = order_rng.permutation(exponential_gaps(n_win + 1))
    due = np.cumsum(gaps)[:n_win] * (seconds / gaps.sum())
    wp, wo = sizes(n_warm) if n_warm else ([], [])
    p, o = sizes(n_win)

    def prompt(n):
        return rng.integers(0, vocab, (int(n),)).astype(np.int32)

    return {"warm": [(None, prompt(a), int(b)) for a, b in zip(wp, wo)],
            "window": [(float(t), prompt(a), int(b))
                       for t, a, b in zip(due, p, o)]}


def check_fits(traffic: dict, max_len: int) -> None:
    """Every request the mix can make must fit the engine."""
    most = traffic["prompt"]["max"] + traffic["output"]["max"]
    if most > max_len:
        raise ValueError(f"traffic: prompt max {traffic['prompt']['max']} "
                         f"+ output max {traffic['output']['max']} exceeds "
                         f"the engine's max_len {max_len}")


def image_batches(traffic: dict, *, seed: int, batch: int):
    """Endless training batches of an ``image_stream`` mix: images of
    unit-normal pixels and uniform labels, every row new, from the seed."""
    rng = np.random.default_rng(seed)
    s, c = traffic["image_size"], traffic["channels"]
    while True:
        yield {"images": rng.standard_normal((batch, s, s, c),
                                             dtype=np.float32),
               "labels": rng.integers(0, traffic["num_labels"],
                                      (batch,)).astype(np.int32)}
