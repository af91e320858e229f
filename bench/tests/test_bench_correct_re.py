"""``correct`` at a size a test run can hold, on the CPU: the harness's
whole run (set-up, window, checks) with the chip check skipped, sound and
with a fault planted under the timed path, and the control in the
program's place.  The limits are the cells' own (``bench/traffic``)."""
import time
from contextlib import nullcontext

import jax
import pytest

from lib import model
from lib.harness import Run

RE_TINY = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
               num_key_value_heads=4, head_dim=16, num_hidden_layers=2,
               vocab_size=257)


def tiny_re():
    spec = model.cell("re-minicpm2b.short")
    return dict(spec, config=dict(spec["config"], **RE_TINY),
                config_entry=dict(spec["config_entry"], name="tiny-re"),
                mix=dict(spec["mix"], batch=4, seq=32))


def run_once(spec, seconds, fault=None, planted=None):
    from lib.harness import kind_module
    run = Run(spec, 2**31 + 17, seconds, False, time.perf_counter(),
              devices=jax.devices())
    with planted(fault) if fault else nullcontext():
        kind_module(run.kind).run_cell(run)
    return run


@pytest.mark.parametrize("fault", [None, "half_batch", "state_unchanged",
                                   "exchange_flipped"])
def test_re_correct_only_when_sound(fault):
    from lib.ensemble import planted
    run = run_once(tiny_re(), 0.5, fault, planted)
    assert run.checks.ok == (fault is None), run.checks.items


def test_re_control_fails():
    from lib.ensemble import Ensemble
    spec = tiny_re()
    run = Run(spec, 2**31 + 17, 0.0, False, time.perf_counter(),
              devices=jax.devices())
    ens = Ensemble(run)
    ens.setup()
    ens.window(0.0)
    ens.free()
    limits = spec["mix"]["limits"]
    sound = ens.compare()
    control = ens.compare(prog=[ens.reference_readings(i, "fp8")
                                for i in range(ens.n)])
    assert all(sound[k] <= limits[k] for k in limits), sound
    assert any(control[k] > limits[k] for k in limits), control
