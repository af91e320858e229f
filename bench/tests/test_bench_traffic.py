"""The benchmark's copy of the request generator gives the program's
windows, and the Metropolis copy the program's exchange decisions."""
import dataclasses

import numpy as np
import pytest

from lib.metropolis import metropolis_swaps as bench_swaps
from lib.traffic import TrafficModel as BenchModel


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_copied_traffic_model_matches_program(seed):
    from repro.serving.traffic import TrafficModel
    kw = dict(seed=seed, window_s=2.0, base_rps=3.0, peak_rps=3.0,
              burst_prob=0.05, latency_frac=0.25, prompt_tokens=512,
              latency_new_tokens=32, throughput_new_tokens=128)
    ours, theirs = BenchModel(**kw), TrafficModel(**kw)
    assert dataclasses.asdict(ours) == dataclasses.asdict(theirs)
    for k in range(40):
        a = [dataclasses.astuple(r) for r in ours.window(k)]
        b = [dataclasses.astuple(r) for r in theirs.window(k)]
        assert a == b
    assert any(ours.window(k) for k in range(40))


@pytest.mark.parametrize("cycle", range(6))
def test_metropolis_copy_matches_program(cycle):
    from repro.plugins.re_exchange import metropolis_swaps
    rng = np.random.default_rng(cycle)
    losses = list(10 + rng.normal(0, 0.05, 6))
    temps = [5e-4 * 1.3 ** i for i in range(6)]
    want = metropolis_swaps(losses, temps, cycle, seed=123)
    got = bench_swaps(losses, temps, cycle, 123)
    assert list(got[0]) == list(want[0])
    assert got[1] == want[1]
