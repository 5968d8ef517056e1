"""The training cell's check, driven on the CPU at a small size with the
harness's look for a chip skipped: a sound run is correct, the control
(the reference in float8 in the program's place) is not, and neither is
a run whose timed path is broken underneath: a step that returns its
state unchanged, or half of each batch left out."""
import pathlib
import sys
import time
import types

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench import common  # noqa: E402
from bench.tests import tiny  # noqa: E402

job = common.load_module(common.BENCH / "jobs" / "train.py")
SEED = 2 ** 31 + 4242


def run_cell(program_cls=None, seed=SEED):
    cfg, mix = tiny.hymba()
    args = types.SimpleNamespace(seed=seed, seconds=0.5, trace=0)
    prog_cls = program_cls or job.Program
    saved = job.Program
    job.Program = prog_cls
    try:
        return job.run(args, {"chips": 1}, cfg, mix, time.perf_counter(), "")
    finally:
        job.Program = saved


class Frozen(job.Program):
    """Each step computes, then hands back the state it was given."""

    def step(self, step):
        import jax
        import jax.numpy as jnp
        keep = jax.tree.map(jnp.copy, self.session.state)
        loss = super().step(step)
        self.session.state = keep
        return loss


class HalfBatch(job.Program):
    """Each step sees the first half of its rows only."""

    def feed(self, step):
        return {k: v[: v.shape[0] // 2] for k, v in super().feed(step).items()}


def test_a_sound_run_is_correct():
    res = run_cell()
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0


@pytest.mark.parametrize("broken", [Frozen, HalfBatch])
def test_a_broken_step_is_not_correct(broken):
    res = run_cell(broken)
    assert not res["correct"], res["checks"]


def test_the_control_in_float8_is_not_correct():
    cfg, mix = tiny.hymba()
    prog = job.Program({"chips": 1}, cfg, mix, SEED)
    specs = prog.specs
    prog.close()
    ref = job.reference_readings(cfg, mix, SEED, specs)
    ctl = job.reference_readings(cfg, mix, SEED, specs, precision="fp8")
    ok, checks = common.judge(job.readings(ctl, ref), mix["limits"])
    assert not ok, checks
