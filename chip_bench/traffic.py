"""The one traffic generator. A mix is a JSON file under ``traffic/``; a
cell's file under ``workloads/`` may set or override its parameters (an
arrival rate, for one). Two kinds:

``backlog``  closed loop: a queue kept ``depth_per_slot`` x ``n_slots``
             deep, refilled after every decode step, so a freed slot is
             refilled at once. Requests have no due time.
``poisson``  open loop: a Poisson process of ``rate_per_s`` over the
             window, given its count: ``rate_per_s`` x seconds requests
             due at independent uniform times in the window, sorted (the
             arrival times of a Poisson process that has n arrivals in a
             window are n independent uniform times), so every seed
             offers the same number of requests.

Every size (input, output length) is a fixed table of quantiles that the
seed puts in a random order: two seeds give the same sizes in another
order, so the seed moves the work as little as it can. A request's input
size comes from the mix's own keys: a ``prompt_tokens`` distribution of
token counts, or audio clips (a fixed ``frames``, or ``clip_seconds`` x
``frames_per_second``). Every request's input is its own, made by the
model family's ``payload`` from the seed and the request's index before
the window opens (``Traffic.prepare``).
"""
from __future__ import annotations

import math
import statistics
from dataclasses import dataclass
from typing import Callable, List, Optional

import numpy as np

#: ``payload(seed, index, size)``: one request's input
Payload = Callable[[int, int, int], np.ndarray]


@dataclass
class Request:
    index: int
    size: int
    max_new: int
    due: Optional[float] = None     # seconds after the window opens
    payload: Optional[np.ndarray] = None


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` values at the mid-quantiles (i + 0.5) / n of ``dist``:
    ``{"dist": "uniform", "low", "high"}`` or
    ``{"dist": "lognormal", "median", "sigma", "low", "high"}`` (clipped);
    ``"integer": true`` rounds to whole numbers."""
    u = (np.arange(n) + 0.5) / n
    if dist["dist"] == "uniform":
        v = dist["low"] + (dist["high"] - dist["low"]) * u
    elif dist["dist"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(x) for x in u])
        v = np.clip(dist["median"] * np.exp(dist["sigma"] * z),
                    dist["low"], dist["high"])
    else:
        raise ValueError(f"unknown distribution {dist['dist']!r}")
    return np.rint(v).astype(int) if dist.get("integer") else v


class Traffic:
    """The requests of one run: ``params`` is the mix merged with the
    cell's overrides; ``payload`` the model family's maker of one
    request's input."""

    def __init__(self, params: dict, seed: int, seconds: float,
                 n_slots: int, payload: Payload):
        self.p = params
        self.kind = params["kind"]
        self.seed = seed % 2**64
        self.seconds = seconds
        self.n_slots = n_slots
        self.payload = payload
        self.rng = np.random.default_rng([self.seed, 1])
        self._made = 0
        self.late = 0              # backlog inputs made inside the window
        if self.kind == "poisson":
            n = max(1, round(params["rate_per_s"] * seconds))
            due = np.sort(self.rng.uniform(0.0, seconds, n))
            sizes = self._sizes(n)
            outs = self.rng.permutation(quantiles(params["out_tokens"], n))
            self.requests = [Request(i, int(sizes[i]), int(outs[i]),
                                     float(due[i])) for i in range(n)]
        elif self.kind == "backlog":
            self.requests = []
            self.depth = params["depth_per_slot"] * n_slots
        else:
            raise ValueError(f"unknown traffic kind {self.kind!r}")

    def _size_table(self, n: int) -> Optional[np.ndarray]:
        """``n`` input sizes at the mix's quantiles, or None where every
        request has the mix's fixed ``frames``."""
        if "prompt_tokens" in self.p:
            return quantiles(self.p["prompt_tokens"], n)
        clip = self.p.get("clip_seconds")
        if clip is None:
            return None
        return np.rint(quantiles(clip, n)
                       * self.p["frames_per_second"]).astype(int)

    def _sizes(self, n: int) -> np.ndarray:
        """``n`` input sizes in a seeded order (a fixed size draws
        nothing)."""
        table = self._size_table(n)
        if table is None:
            return np.full(n, self.p["frames"])
        return self.rng.permutation(table)

    def size_values(self) -> List[int]:
        """Every input size the run's requests can have, in order."""
        if self.kind == "poisson":
            return sorted({r.size for r in self.requests})
        table = self._size_table(self.p["table"])
        return [self.p["frames"]] if table is None else \
            sorted({int(x) for x in table})

    def _backlog_block(self) -> List[Request]:
        """The next ``table`` backlog requests: the length table in a
        fresh seeded order."""
        n = self.p["table"]
        outs = self.rng.permutation(quantiles(self.p["out_tokens"], n))
        sizes = self._sizes(n)
        base = len(self.requests)
        return [Request(base + i, int(sizes[i]), int(outs[i]))
                for i in range(n)]

    def prepare(self, n: Optional[int] = None) -> None:
        """Make the inputs of the first ``n`` requests (all of an open-loop
        mix) ahead of the window."""
        if self.kind == "backlog":
            while len(self.requests) < n:
                self.requests.extend(self._backlog_block())
        n = len(self.requests) if n is None else n
        for r in self.requests[self._made:n]:
            r.payload = self.payload(self.seed, r.index, r.size)
        self._made = max(self._made, n)

    def request(self, i: int) -> Request:
        """Backlog request ``i``, made now if ``prepare`` did not reach it
        (``late`` counts those)."""
        if i >= self._made:
            self.late += 1
            self.prepare(i + 1)
        return self.requests[i]

    def warm_request(self, size: int) -> Request:
        """A request of ``size`` outside the measured sequence, for
        warming up."""
        return Request(-1, size, 2, payload=self.payload(self.seed, 2**32,
                                                         size))


def expected_backlog_requests(tokens_per_s: float, seconds: float,
                              out_tokens: dict) -> int:
    """Requests a closed-loop window is expected to start: its rate of
    tokens over the mean length, plus the backlog and a margin."""
    mean = float(np.mean(quantiles(out_tokens, 512)))
    return int(math.ceil(1.5 * tokens_per_s * seconds / mean))
