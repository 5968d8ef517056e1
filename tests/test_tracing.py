"""The names the program puts on its work for a profiler trace: the
device-side layer scopes (`jax.named_scope`, read back from the compiled
HLO's `op_name` metadata) and the host-side `repro:` spans with the
serving engine's per-tick phase seconds."""
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_reduced
from repro.engine import (EngineConfig, GenerationRequest, ServeEngine,
                          TrainSession)
from repro.models import build_model
from repro.runtime.spans import PREFIX, span

TICK_PHASES = {"serve.admit", "serve.grow", "serve.decode", "serve.pull",
               "serve.commit"}


def scopes_in(hlo_text: str) -> set:
    """Every name-stack segment of the compiled program's ops."""
    return {seg for name in re.findall(r'op_name="([^"]*)"', hlo_text)
            for seg in re.split(r"[/;]", name)}


def host_spans(fn, tmp_path) -> list:
    """Names of the `repro:` host spans a profiler trace of fn() holds."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    pd = jax.profiler.ProfileData.from_file(path)
    return [e.name for plane in pd.planes for line in plane.lines
            for e in line.events if e.name.startswith(PREFIX)]


@pytest.fixture(scope="module")
def hybrid_session():
    return TrainSession.from_config(EngineConfig(
        arch="hymba-1p5b", reduced=True, accum_steps=2, seq_len=32,
        global_batch=4, optimizer="adam"), callbacks=[])


@pytest.fixture(scope="module")
def hybrid_step_hlo(hybrid_session):
    s = hybrid_session
    return s._step_fn.lower(s.state, s.batch(0)).compile().as_text()


def test_train_step_names_every_layer_scope(hybrid_step_hlo):
    assert {"attention", "mamba", "mlp", "accumulate",
            "optimizer"} <= scopes_in(hybrid_step_hlo)


def test_backward_ops_keep_the_layer_scope(hybrid_step_hlo):
    names = re.findall(r'op_name="([^"]*)"', hybrid_step_hlo)
    assert any("transpose(jvp())" in n and "/mlp/" in n for n in names)
    assert any("transpose(jvp())" in n and "/attention/" in n
               for n in names)


def test_train_step_runs_in_nested_host_spans(hybrid_session, tmp_path):
    got = host_spans(lambda: hybrid_session.step(), tmp_path)
    assert sorted(got) == sorted(PREFIX + n for n in (
        "train.step", "train.dispatch", "train.pull"))


def paged_mla_engine():
    model = build_model(get_reduced("minicpm3-4b"),
                        compute_dtype=jnp.float32, attn_chunk=8)
    params = model.init(jax.random.key(0))
    cfg = EngineConfig(max_slots=2, max_len=48, kv_layout="paged",
                       page_size=8)
    return ServeEngine(cfg, model, None, params)


def test_paged_mla_decode_step_names_attention_and_mlp():
    eng = paged_mla_engine()
    assert eng.paged
    text = eng._decode.lower(eng.params, jnp.asarray(eng._tokens),
                             eng.cache).compile().as_text()
    assert {"attention", "mlp"} <= scopes_in(text)


def test_engine_ticks_fill_phase_seconds_and_the_slowest_tick(tmp_path):
    eng = paged_mla_engine()
    rng = np.random.RandomState(0)
    V = eng.model.cfg.vocab_size
    for n in (12, 9):
        eng.submit(GenerationRequest(
            prompt=rng.randint(0, V, n).astype(np.int32),
            max_new_tokens=10))
    ticks = []

    def drive():
        while eng.step():
            ticks.append(1)
        ticks.append(1)

    names = host_spans(drive, tmp_path)
    phases = eng.stats["tick_phase_s"]
    assert set(phases) == TICK_PHASES
    assert all(v > 0 for v in phases.values())
    slowest = eng.stats["slowest_tick"]
    assert set(slowest["phases"]) <= TICK_PHASES
    assert slowest["total_s"] >= sum(slowest["phases"].values())
    # one serve.step span per tick, the phases inside them
    assert names.count(PREFIX + "serve.step") == len(ticks)
    assert {n[len(PREFIX):] for n in names} == TICK_PHASES | {"serve.step"}
    assert eng.throughput()["tick_phase_s"] == phases


def test_a_span_adds_its_seconds_to_the_given_dict():
    into = {}
    for _ in range(2):
        with span("x", into):
            pass
    with span("y"):
        pass
    assert set(into) == {"x"} and into["x"] > 0


def test_serve_cli_prints_the_tick_phases_and_the_slowest_tick(capsys):
    from repro.launch.serve import main
    main(["--arch", "minicpm3-4b", "--reduced", "--requests", "2",
          "--prompt-len", "8", "--gen", "4", "--max-slots", "2"])
    lines = capsys.readouterr().out.splitlines()
    [phases] = [ln for ln in lines if ln.startswith("[serve] tick phases")]
    [slowest] = [ln for ln in lines if ln.startswith("[serve] slowest tick")]
    for name in TICK_PHASES:
        assert f" {name}_s=" in phases
    assert "total_s=" in slowest and " serve.admit_s=" in slowest
