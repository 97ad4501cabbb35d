"""Closed-loop request streams, made from a traffic file and a seed.

A traffic file (``bench/traffic/<name>.json``) gives the engine settings, the
number of clients and the length distributions.  Every seed gets the same
work: each client's R requests take the prompt and output lengths at the
quantiles (i + 0.5) / R of their distributions, i = 0 .. R-1, and the seed
only chooses their order and draws the token ids and each request's
sampling seed.  The order keeps every window alike, since a window serves
only each client's first few requests: a client walks the quantiles in
bit-reversed order (each stretch of 2, 4, 8 ... requests takes one from
each of as many equal strata), started at a rotation of its own, and the
clients' rotations are spread evenly over the quantiles, so at every step
of the walk the clients together cover them evenly.  The set of rotations
is the same for every seed; the seed deals them to the clients, prompt and
output lengths apart.  The requests in flight when the window opens are
each client's first; their outputs follow ``first_output`` (a residual life,
so that they end at staggered times as in a steady state).
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class Request:
    client: int
    prompt: list
    max_new: int
    seed: int


def quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` whole lengths at the quantiles (i + 0.5) / n of ``spec``:
    ``uniform`` or ``loguniform`` between ``min`` and ``max``."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = float(spec["min"]), float(spec["max"])
    if spec["dist"] == "uniform":
        x = lo + u * (hi - lo)
    elif spec["dist"] == "loguniform":
        x = lo * (hi / lo) ** u
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.rint(x).astype(int)


def bit_reversed(R: int) -> np.ndarray:
    """0 .. R-1 ordered by their bit-reversed binary form."""
    bits = max(1, (R - 1).bit_length())
    return np.array(sorted(range(R), key=lambda i: int(format(i, f"0{bits}b")[::-1], 2)))


class Clients:
    """Each client's request sequence; ``next(c)`` gives client c's next."""

    def __init__(self, traffic: dict, vocab: int, seed: int):
        self.rng = np.random.default_rng(seed)
        self.vocab = vocab
        n, R = traffic["clients"], traffic["requests_per_client"]
        prompts = quantiles(traffic["prompt"], R)
        outputs = quantiles(traffic["output"], R)
        first_out = self.rng.permutation(quantiles(traffic["first_output"], n))
        walk = bit_reversed(R)
        rot_p, rot_o = self._rotations(n, R), self._rotations(n, R)
        self.plan = []
        for c in range(n):
            p, o = prompts[(walk + rot_p[c]) % R], outputs[(walk + rot_o[c]) % R]
            o[0] = first_out[c]
            self.plan.append(list(zip(p.tolist(), o.tolist())))
        self.taken = [0] * n

    def _rotations(self, n: int, R: int) -> np.ndarray:
        """Each client's start on the quantiles: the same evenly spread set
        of starts for every seed, dealt to the clients in an order drawn
        from the seed."""
        step = max(R // n, 1)
        k = self.rng.permutation(n)
        return (k * step + k % step) % R

    @property
    def n(self) -> int:
        return len(self.plan)

    def next(self, c: int) -> Request:
        plan = self.plan[c]
        p, o = plan[self.taken[c] % len(plan)]
        self.taken[c] += 1
        return Request(c, self.rng.integers(0, self.vocab, size=p).tolist(), o,
                       int(self.rng.integers(0, 2**62)))
