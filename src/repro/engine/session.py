"""TrainSession / ServeSession — the one-line integration the paper sells.

The paper's §4.1 usability claim is

    opt = hvd.DistributedOptimizer(opt, op=hvd.Adasum)

Here the whole setup (model, mesh, policy, combiner, data, checkpoints,
monitoring) collapses to:

    from repro.engine import EngineConfig, TrainSession
    session = TrainSession.from_config(
        EngineConfig(arch="hymba-1p5b", reduced=True, combine="adasum"))
    session.fit(100)

`fit` absorbs the training loop that used to live in launch/train.py:
resume-from-latest, periodic atomic checkpoints, SIGTERM save, straggler
monitoring, and (for drills) failure injection — all expressed as
pluggable callbacks, scheduled by `repro.engine.pipeline.StepPipeline`
(batch prefetch and checkpoint writes overlap the device step).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import AsyncCheckpointManager, CheckpointManager
from repro.configs.base import get_config, get_reduced, pad_heads_for_tp
from repro.control.noise import STAT_KEYS
from repro.control.telemetry import run_fingerprint
from repro.data import make_source
from repro.launch.mesh import make_local_mesh
from repro.models import build_model
from repro.models.api import Model
from repro.runtime import StepMonitor, FailureInjector
from repro.runtime.spans import span

from .build import Runtime, build_runtime, make_serve_step
from .config import EngineConfig

PyTree = Any


# ------------------------------------------------------------------ callbacks

class Callback:
    """Hook points around the training loop. All default to no-ops."""

    def on_fit_start(self, session: "TrainSession", start_step: int): ...

    def on_step_start(self, session: "TrainSession", step: int): ...

    def on_step_end(self, session: "TrainSession", step: int,
                    metrics: Dict[str, float], dt: float): ...

    def on_fit_end(self, session: "TrainSession",
                   history: List[Dict[str, float]]): ...


class LoggingCallback(Callback):
    def __init__(self, every: int = 10):
        self.every = every

    def on_step_end(self, session, step, metrics, dt):
        last = step == session.config.steps - 1
        if step % self.every == 0 or last:
            print(f"[train] step {step:5d} loss {metrics['loss']:.4f} "
                  f"{dt*1e3:.0f}ms span={session.runtime.span} "
                  f"combine={session.config.combine}")


class CheckpointCallback(Callback):
    """Periodic atomic checkpoints + final save via the session manager."""

    def __init__(self, every: int = 50):
        self.every = every

    def on_step_end(self, session, step, metrics, dt):
        # every <= 0: periodic saves off — only the final on_fit_end
        # save (and driver-side save_sync at elastic/resize boundaries)
        if session.checkpoint and self.every > 0 \
                and (step + 1) % self.every == 0:
            session.save(step + 1)

    def on_fit_end(self, session, history):
        if session.checkpoint and history:
            session.save(int(history[-1]["step"]) + 1)


class StragglerCallback(Callback):
    """Feeds step wall-times to the robust z-score StepMonitor."""

    def __init__(self, monitor: Optional[StepMonitor] = None):
        self.monitor = monitor or StepMonitor()

    def on_step_end(self, session, step, metrics, dt):
        self.monitor.observe(dt)

    def on_fit_end(self, session, history):
        print(f"[train] monitor={self.monitor.summary()}")


class FailureInjectionCallback(Callback):
    """Recovery drills: raise at scheduled steps (simulated node loss)."""

    def __init__(self, fail_at: Sequence[int]):
        self.injector = FailureInjector(list(fail_at))

    def on_step_start(self, session, step):
        self.injector.check(step)


def default_callbacks(cfg: EngineConfig,
                      fail_at: Sequence[int] = ()) -> List[Callback]:
    cbs: List[Callback] = [LoggingCallback(cfg.log_every),
                           StragglerCallback()]
    if cfg.ckpt_dir:
        cbs.append(CheckpointCallback(cfg.ckpt_every))
    if fail_at:
        cbs.insert(0, FailureInjectionCallback(fail_at))
    return cbs


# ---------------------------------------------------------------- TrainSession

class TrainSession:
    """One training run: config -> (model, mesh, runtime, data, state)."""

    def __init__(self, config: EngineConfig, model: Model,
                 mesh: jax.sharding.Mesh, runtime: Runtime, source,
                 callbacks: Optional[List[Callback]] = None,
                 checkpoint: Optional[CheckpointManager] = None):
        self.config = config
        self.model = model
        self.mesh = mesh
        self.runtime = runtime
        self.source = source
        self.callbacks = (default_callbacks(config) if callbacks is None
                          else list(callbacks))
        self.checkpoint = checkpoint
        self.state: PyTree = runtime.init_state(jax.random.key(0))
        self._step_fn = jax.jit(runtime.train_step, donate_argnums=(0,))
        self._delayed_stream = None   # set by use_delayed_stream()
        self._last_stats: Dict[str, float] = {}   # latest CombineStats

    # ------------------------------------------------------------ construction
    @classmethod
    def from_config(cls, config: EngineConfig, *,
                    model: Optional[Model] = None,
                    mesh: Optional[jax.sharding.Mesh] = None,
                    callbacks: Optional[List[Callback]] = None
                    ) -> "TrainSession":
        config.validate()
        if mesh is None:
            model_mesh = config.model_mesh
            data_size = config.data_mesh or max(
                1, len(jax.devices()) // model_mesh)
            mesh = make_local_mesh(data_size, model_mesh)
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        dp_total = int(np.prod([s for a, s in sizes.items()
                                if a != "model"]))

        if model is None:
            if not config.arch:
                raise ValueError("EngineConfig.arch is empty — pass a "
                                 "built Model via from_config(model=...)")
            mcfg = (get_reduced(config.arch) if config.reduced
                    else get_config(config.arch))
            if config.pad_heads:
                mcfg = pad_heads_for_tp(mcfg, sizes.get("model", 1))
            model = build_model(
                mcfg, attn_chunk=min(config.attn_chunk, config.seq_len),
                param_dtype=jnp.dtype(config.param_dtype))

        # span can't exceed dp (small host meshes): clamp to one lane per
        # DP rank, as launch/train.py always did
        if config.span > dp_total:
            config = dataclasses.replace(config, span=0)
        config.validate(dp_total)

        runtime = build_runtime(model, mesh, config.run_policy(),
                                lr=config.lr, strict=config.strict)
        source = make_source(config.data_config(model.cfg.vocab_size),
                             model.cfg)
        ckpt_cls = (AsyncCheckpointManager if config.async_checkpoint
                    else CheckpointManager)
        ckpt = ckpt_cls(config.ckpt_dir) if config.ckpt_dir else None
        return cls(config, model, mesh, runtime, source,
                   callbacks=callbacks, checkpoint=ckpt)

    # -------------------------------------------------------------- metadata
    def run_metadata(self) -> Dict[str, Any]:
        """What actually ran — the resolved (post-fallback) combine path
        plus the run's topology. Benchmarks record this next to their
        numbers so a 'fused' result can't silently come from the
        reference tree (the span == dp fallback)."""
        sizes = dict(zip(self.mesh.axis_names,
                         (int(s) for s in self.mesh.devices.shape)))
        rt = self.runtime
        return {"arch": self.config.arch or self.model.cfg.name,
                "combine": self.config.combine,
                "backend": self.config.backend,
                "combine_path": rt.combine_path,
                "span": rt.span,
                "dp": rt.dp_total,
                "local_steps": self.config.local_steps,
                "combine_delay": self.config.combine_delay,
                "devices": int(self.mesh.devices.size),
                "mesh": sizes,
                # CombineStats observability: whether the step emits the
                # grad-noise/orthogonality/gain metrics, and the latest
                # values seen (empty before the first step / when off) —
                # exposed even when the adaptive controller is off
                "stats_enabled": rt.combine_stats,
                "combine_stats": dict(self._last_stats),
                "adaptive_batch": self.config.adaptive_batch,
                "global_batch": self.config.global_batch,
                "lr": self.config.lr,
                "resilience": self._resilience_metadata(),
                **run_fingerprint(self.config)}

    def _resilience_metadata(self) -> Dict[str, Any]:
        """Fault-recovery accounting for the run: checkpoint restore
        fallbacks + quarantined steps (from the manager) and elastic
        restart/grow-back counts (attached by fit_elastic). Benchmarks
        record this so a result that survived faults says so."""
        log = getattr(self, "elastic_log", None) or {}
        out: Dict[str, Any] = {
            # cumulative across elastic rebuilds: earlier sessions'
            # counters are banked in elastic_log by fit_elastic
            "restore_fallbacks": log.get("prior_restore_fallbacks", 0),
            "quarantined_steps": list(log.get("prior_quarantined", [])),
            "restarts": log.get("restarts", 0),
            "grow_backs": log.get("grow_backs", 0)}
        if self.checkpoint is not None:
            out["restore_fallbacks"] += self.checkpoint.restore_fallbacks
            out["quarantined_steps"] += [
                q["step"] for q in self.checkpoint.quarantined]
        return out

    def use_delayed_stream(self, comm_delay: float = 0.0):
        """Route steps through a host-level `DelayedCombineStream`: the
        pending-delta exchange runs on a background thread (optionally
        behind `comm_delay` seconds of injected interconnect latency)
        while the local step computes, and metrics gain compute_s /
        combine_wait_s. Bitwise-identical states to the default
        single-program delayed step. Needs combine_delay=1."""
        from repro.runtime import DelayedCombineStream
        self._delayed_stream = DelayedCombineStream(
            self.runtime, comm_delay=comm_delay)
        return self._delayed_stream

    # ------------------------------------------------------------------ steps
    def batch(self, step: int) -> Dict[str, jnp.ndarray]:
        """The deterministic batch for `step` (pure function of config)."""
        return {k: jnp.asarray(v)
                for k, v in self.source.batch(step).items()}

    def step(self, batch: Optional[Dict[str, jnp.ndarray]] = None
             ) -> Dict[str, float]:
        """One optimizer step; advances self.state. With no batch, pulls
        the deterministic batch for the current step counter. Runs in a
        `repro:train.step` host span, with `train.dispatch` (the step
        call) and `train.pull` (the metrics to the host) inside it."""
        with span("train.step"):
            if batch is None:
                batch = self.batch(int(jax.device_get(self.state["step"])))
            with span("train.dispatch"):
                if self._delayed_stream is not None:
                    self.state, metrics = self._delayed_stream.step(
                        self.state, batch)
                else:
                    self.state, metrics = self._step_fn(self.state, batch)
            with span("train.pull"):
                out = {k: float(jax.device_get(v))
                       for k, v in metrics.items()}
            stats = {k: out[k] for k in STAT_KEYS if k in out}
            if stats:
                self._last_stats = stats
            return out

    def fit(self, steps: Optional[int] = None) -> List[Dict[str, float]]:
        """Train to `steps` total (resuming from the latest checkpoint if
        one exists). Returns the per-step history.

        A thin wrapper: the loop itself — prefetch overlap, resume
        decision, callback dispatch, elastic flag consumption, end-of-run
        barriers — lives in `repro.engine.pipeline.StepPipeline`.
        """
        from .pipeline import StepPipeline
        steps = self.config.steps if steps is None else steps
        self.config = dataclasses.replace(self.config, steps=steps)
        return StepPipeline(self).run()

    # ------------------------------------------------------------ checkpoints
    def save(self, step: Optional[int] = None):
        assert self.checkpoint is not None, "no ckpt_dir configured"
        step = (int(jax.device_get(self.state["step"]))
                if step is None else step)
        return self.checkpoint.save(step, self.state)

    def save_sync(self, step: Optional[int] = None):
        """save() + barrier: the checkpoint is durably on disk on return
        (the async writer only guarantees that at the next barrier).
        The path for SIGTERM handlers and elastic restarts."""
        path = self.save(step)
        wait = getattr(self.checkpoint, "wait", None)
        if wait is not None:
            wait()
        return path

    def close(self):
        """Release background resources (the async checkpoint writer and
        the delayed-combine exchange thread). The session is done after
        this — a later save would fail."""
        if self._delayed_stream is not None:
            self._delayed_stream.close()
            self._delayed_stream = None
        if self.checkpoint is not None:
            close = getattr(self.checkpoint, "close", None)
            if close is not None:
                close()

    def restore(self, step: Optional[int] = None) -> int:
        """Restore state from the latest (or given) checkpoint, if any.
        Returns the resumed step (0 when nothing to restore)."""
        assert self.checkpoint is not None, "no ckpt_dir configured"
        if self.checkpoint.latest_step() is None and step is None:
            return 0
        # Re-place restored leaves on the live state's shardings: the
        # manifest hands back host-local arrays, and stepping from those
        # compiles a single-device executable whose reduction order
        # differs from the mesh-sharded one — resume would drift from
        # the uninterrupted run by float rounding every step.
        template = self.state
        restored = self.checkpoint.restore(template, step)
        self.state = jax.tree.map(
            lambda v, old: (jax.device_put(v, old.sharding)
                            if hasattr(old, "sharding") else v),
            restored, template)
        start = int(jax.device_get(self.state["step"]))
        print(f"[train] resumed from step {start}")
        return start


# ---------------------------------------------------------------- ServeSession

class ServeSession:
    """Legacy batched-serving surface, now a thin compat wrapper over
    `repro.engine.serving.ServeEngine`: `generate(prompts, gen_len)`
    submits one request per row and drains the engine (fused prefill +
    slotted continuous batching). `stepped_prefill=True` keeps the old
    one-token-at-a-time loop — the bitwise reference the equivalence
    tests pin the fused path against. Frontend/enc-dec models (per-batch
    encoder state, not per-slot) always take the stepped path."""

    def __init__(self, config: EngineConfig, model: Model,
                 mesh: jax.sharding.Mesh, params: PyTree,
                 checkpoint: Optional[CheckpointManager] = None,
                 loaded_step: Optional[int] = None):
        self.config = config
        self.model = model
        self.mesh = mesh
        self.params = params
        self.checkpoint = checkpoint
        self._loaded_step = loaded_step
        self._step = jax.jit(make_serve_step(model), donate_argnums=(2,))
        self._engine: Optional[Any] = None      # lazily-built ServeEngine

    @classmethod
    def from_config(cls, config: EngineConfig, *,
                    model: Optional[Model] = None,
                    mesh: Optional[jax.sharding.Mesh] = None,
                    params: Optional[PyTree] = None,
                    attn_chunk: int = 64) -> "ServeSession":
        # shared serve bootstrap (ServeEngine.from_config uses it too):
        # with ckpt_dir, serves the trained weights via the params-only
        # restore against the path-indexed manifest
        from .serving.engine import resolve_serve_parts
        model, mesh, params, checkpoint, loaded_step = resolve_serve_parts(
            config, model=model, mesh=mesh, params=params,
            attn_chunk=attn_chunk)
        return cls(config, model, mesh, params, checkpoint=checkpoint,
                   loaded_step=loaded_step)

    # -------------------------------------------------------------- engine
    def engine(self, max_len: Optional[int] = None):
        """The ServeEngine behind this session (one engine, lazily built,
        re-built larger when a call needs more cache capacity). Inherits
        the session's checkpoint manager, so `hot_reload=True` in the
        config works here too. Prefer it directly for request-level
        serving (streaming, staggered arrivals)."""
        from .serving import ServeEngine
        need = max_len or self.config.max_len or self.config.seq_len
        if self._engine is None or self._engine.max_len < need:
            cap = 1 << (need - 1).bit_length()     # pow2: bounds rebuilds
            cfg = dataclasses.replace(self.config, max_len=cap)
            self._engine = ServeEngine(cfg, self.model, self.mesh,
                                       self.params,
                                       checkpoint=self.checkpoint,
                                       loaded_step=self._loaded_step)
        return self._engine

    # ------------------------------------------------------------ generate
    def generate(self, prompts: jnp.ndarray, gen_len: int,
                 max_len: Optional[int] = None,
                 frontend_embeds=None,
                 stepped_prefill: bool = False) -> jnp.ndarray:
        """prompts: [B, T] int32. Returns [B, T+gen_len]."""
        B, T = prompts.shape
        max_len = max_len or (T + gen_len + 1)
        cfg = self.model.cfg
        if (stepped_prefill or frontend_embeds is not None
                or cfg.is_encoder_decoder or cfg.frontend != "none"):
            return self._generate_stepped(prompts, gen_len, max_len,
                                          frontend_embeds)
        from .serving import GenerationRequest
        eng = self.engine(max_len)
        handles = [eng.submit(GenerationRequest(
            prompt=np.asarray(prompts[i]), max_new_tokens=gen_len))
            for i in range(B)]
        eng.drain()
        return jnp.asarray(np.stack([h.output for h in handles]))

    def _generate_stepped(self, prompts, gen_len, max_len,
                          frontend_embeds=None) -> jnp.ndarray:
        """The pre-ServeEngine loop: prompt fed one token at a time
        through the jitted decode step (T dispatches), then greedy
        decode. Cache-exact — the fused paths are tested against it."""
        B, T = prompts.shape
        cfg = self.model.cfg
        if cfg.is_encoder_decoder:
            cache = self.model.init_cache(self.params, B, max_len,
                                          frontend_embeds=frontend_embeds)
        else:
            cache = self.model.init_cache(self.params, B, max_len)
        nxt = prompts[:, :1]
        for t in range(T):
            nxt, cache = self._step(self.params, prompts[:, t:t + 1], cache)
        cur = nxt
        gen = []
        for _ in range(gen_len):
            gen.append(cur)
            cur, cache = self._step(self.params, cur, cache)
        return jnp.concatenate([prompts] + gen, axis=1)
