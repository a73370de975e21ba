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

Every size (clip, output length) is a fixed table of quantiles that the
seed puts in a random order: two seeds give the same sizes in another
order, so the seed moves the work as little as it can. Every request's
audio is its own: Gaussian log-mel frames from the seed and the
request's index, made before the window opens (``Traffic.prepare``).
"""
from __future__ import annotations

import math
import statistics
from dataclasses import dataclass
from typing import List, Optional

import numpy as np

@dataclass
class Request:
    index: int
    frames: int
    max_new: int
    due: Optional[float] = None     # seconds after the window opens
    mel: Optional[np.ndarray] = None


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
    cell's overrides; ``n_mels`` the model's mel channels."""

    def __init__(self, params: dict, seed: int, seconds: float,
                 n_slots: int, n_mels: int):
        self.p = params
        self.kind = params["kind"]
        self.seed = seed % 2**64
        self.seconds = seconds
        self.n_slots = n_slots
        self.n_mels = n_mels
        self.rng = np.random.default_rng([self.seed, 1])
        self._made = 0
        self.late = 0              # backlog audio made inside the window
        if self.kind == "poisson":
            n = max(1, round(params["rate_per_s"] * seconds))
            due = np.sort(self.rng.uniform(0.0, seconds, n))
            frames = self._frames(n)
            outs = self.rng.permutation(quantiles(params["out_tokens"], n))
            self.requests = [Request(i, int(frames[i]), int(outs[i]),
                                     float(due[i])) for i in range(n)]
        elif self.kind == "backlog":
            self.requests = []
            self.depth = params["depth_per_slot"] * n_slots
        else:
            raise ValueError(f"unknown traffic kind {self.kind!r}")

    def _frames(self, n: int) -> np.ndarray:
        clip = self.p.get("clip_seconds")
        if clip is None:
            return np.full(n, self.p["frames"])
        secs = self.rng.permutation(quantiles(clip, n))
        return np.rint(secs * self.p["frames_per_second"]).astype(int)

    def _mel(self, index: int, frames: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, 2, index])
        return rng.standard_normal((frames, self.n_mels), np.float32)

    def _backlog_block(self) -> List[Request]:
        """The next ``table`` backlog requests: the length table in a
        fresh seeded order."""
        n = self.p["table"]
        outs = self.rng.permutation(quantiles(self.p["out_tokens"], n))
        frames = self._frames(n)
        base = len(self.requests)
        return [Request(base + i, int(frames[i]), int(outs[i]))
                for i in range(n)]

    def prepare(self, n: Optional[int] = None) -> None:
        """Make the audio of the first ``n`` requests (all of an open-loop
        mix) ahead of the window."""
        if self.kind == "backlog":
            while len(self.requests) < n:
                self.requests.extend(self._backlog_block())
        n = len(self.requests) if n is None else n
        for r in self.requests[self._made:n]:
            r.mel = self._mel(r.index, r.frames)
        self._made = max(self._made, n)

    def request(self, i: int) -> Request:
        """Backlog request ``i``, made now if ``prepare`` did not reach it
        (``late`` counts those)."""
        if i >= self._made:
            self.late += 1
            self.prepare(i + 1)
        return self.requests[i]

    def warm_request(self) -> Request:
        """A request outside the measured sequence, for warming up."""
        frames = int(self.p.get("frames", 50))
        return Request(-1, frames, 2, mel=self._mel(2**32, frames))


def expected_backlog_requests(tokens_per_s: float, seconds: float,
                              out_tokens: dict) -> int:
    """Requests a closed-loop window is expected to start: its rate of
    tokens over the mean length, plus the backlog and a margin."""
    mean = float(np.mean(quantiles(out_tokens, 512)))
    return int(math.ceil(1.5 * tokens_per_s * seconds / mean))
