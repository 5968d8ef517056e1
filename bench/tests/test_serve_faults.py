"""The serving cell's check, driven on the CPU at a small size with the
harness's look for a chip skipped: a sound run is correct, a run whose
tokens are altered where the engine commits them is not, and the
control (the reference in float8 choosing the tokens) reads far above
the sound run."""
import pathlib
import sys
import time
import types

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench import common  # noqa: E402
from bench.tests import tiny  # noqa: E402

job = common.load_module(common.BENCH / "jobs" / "serve.py")
SEED = 2 ** 31 + 515


def run_cell(program_cls=None):
    cfg, mix = tiny.minicpm3()
    args = types.SimpleNamespace(seed=SEED, seconds=3.0, trace=0)
    saved = job.Program
    job.Program = program_cls or job.Program
    try:
        return job.run(args, {"chips": 1}, cfg, mix, time.perf_counter(), "")
    finally:
        job.Program = saved


class AlteredToken(job.Program):
    """The engine commits token + 1 in place of the one it computed."""

    def __init__(self, *a, **k):
        super().__init__(*a, **k)
        commit, vocab = self.engine._commit, self.mcfg.vocab_size
        self.engine._commit = lambda h, t: commit(h, (t + 1) % vocab)


def test_a_sound_run_is_correct_and_the_control_reads_far_above_it():
    """The control (the reference in float8 choosing each token) on the
    sound run's own checked requests: at this size its gap stays under
    the cell's limit, which was set from chip readings at the cell's size
    (PERF.md), so it is held to three times the sound run's reading."""
    import numpy as np
    res = run_cell()
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    seqs, served, specs = res["ctx"]["checked"]
    cfg, _ = tiny.minicpm3()
    ref = job.reference_logits(cfg, specs, SEED, seqs)
    ctl = job.reference_logits(cfg, specs, SEED, seqs, precision="fp8")
    sound = max(job.gaps_of(ref, served))
    control = max(job.gaps_of(ref, [np.asarray(c).argmax(-1) for c in ctl]))
    assert control > 0 and control >= 3 * sound, (sound, control)


def test_an_altered_token_is_not_correct():
    res = run_cell(AlteredToken)
    assert not res["correct"], res["checks"]
