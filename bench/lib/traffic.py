"""The benchmark's copy of the program's request generator
(``repro.serving.traffic.TrafficModel``): a diurnal raised cosine between
``base_rps`` and ``peak_rps`` with Bernoulli bursts; window ``k``'s requests
are drawn from ``numpy.random.default_rng((seed, k))``.  The program's
``serve.decode`` regenerates a window's requests from the same parameters,
so the field names and the draws must stay those of the program; a test
holds the two to the same windows."""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List, Optional

import numpy as np


@dataclass(frozen=True)
class Request:
    rid: int
    window: int
    sla: str
    offset_s: float
    prompt_tokens: int
    max_new_tokens: int


@dataclass(frozen=True)
class TrafficModel:
    seed: int = 0
    window_s: float = 30.0
    base_rps: float = 2.0
    peak_rps: float = 8.0
    period_s: float = 3600.0
    burst_prob: float = 0.05
    burst_mult: float = 4.0
    latency_frac: float = 0.25
    prompt_tokens: int = 128
    latency_new_tokens: int = 16
    throughput_new_tokens: int = 96
    bytes_per_token: int = 4

    def rate(self, k: int) -> float:
        t = (k + 0.5) * self.window_s
        diurnal = 0.5 * (1.0 - math.cos(2.0 * math.pi * t / self.period_s))
        return self.base_rps + (self.peak_rps - self.base_rps) * diurnal

    def window(self, k: int) -> List[Request]:
        rng = np.random.default_rng((self.seed, k))
        rate = self.rate(k)
        if rng.random() < self.burst_prob:
            rate *= self.burst_mult
        n = int(rng.poisson(rate * self.window_s))
        offsets = np.sort(rng.uniform(0.0, self.window_s, n))
        is_lat = rng.random(n) < self.latency_frac
        out = []
        for i in range(n):
            sla = "latency" if is_lat[i] else "throughput"
            out.append(Request(
                rid=k * 1_000_000 + i, window=k, sla=sla,
                offset_s=float(offsets[i]), prompt_tokens=self.prompt_tokens,
                max_new_tokens=(self.latency_new_tokens if sla == "latency"
                                else self.throughput_new_tokens)))
        return out

    def requests(self, k: int, sla: Optional[str] = None) -> List[Request]:
        reqs = self.window(k)
        return reqs if sla is None else [r for r in reqs if r.sla == sla]
