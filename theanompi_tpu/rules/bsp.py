"""BSP — synchronous data-parallel training.

Parity rebuild of the reference's BSP worker process (SURVEY.md §2.3,
§3.2 — mount empty, no file:line): per-iteration train step +
gradient allreduce, per-epoch validation, ``adjust_hyperp``, rank-0
checkpoint.  Here the N worker processes collapse into one SPMD
program over the mesh's ``data`` axis; the exchange is fused into the
jitted step (parallel/bsp.py), so this module is just the epoch
driver: data staging, validation, LR schedule, checkpoint/resume,
recorder bookkeeping.
"""

from __future__ import annotations

import os
import time

from theanompi_tpu import monitor
from theanompi_tpu.models.base import TpuModel
from theanompi_tpu.parallel.mesh import data_mesh
from theanompi_tpu.rules.base import Rule, resolve_model_class
from theanompi_tpu.utils.checkpoint import Checkpointer
from theanompi_tpu.utils.profiling import StepProfiler
from theanompi_tpu.utils.recorder import Recorder


def run_bsp_session(model: TpuModel, sync_type: str = "avg",
                    resume: bool = False, recorder: Recorder | None = None,
                    max_epochs: int | None = None,
                    checkpoint: bool = True,
                    profile_dir: str | None = None,
                    monitor_dir: str | None = None) -> dict:
    """The BSP epoch loop (callable directly, e.g. from the launcher).

    ``profile_dir`` (or env ``THEANOMPI_TPU_PROFILE``) captures a
    jax.profiler trace of the first steps — utils/profiling.py.
    ``monitor_dir`` (or env ``THEANOMPI_TPU_MONITOR``) activates the
    telemetry subsystem: step-time histogram, per-phase spans,
    heartbeat/watchdog, and a postmortem dump if the loop dies
    (docs/OBSERVABILITY.md)."""
    cfg = model.config
    # multi-host: rank = host index, so only host 0 prints / writes the
    # JSONL curve (the reference's rank-0 gating, SURVEY.md §3.5)
    host = model.host_rank
    recorder = recorder or Recorder(
        rank=host, size=model.n_workers, print_freq=cfg.print_freq,
        save_dir=cfg.snapshot_dir if host == 0 else None,
        flops_per_sample=model.train_flops_per_sample)
    profiler = StepProfiler(profile_dir)
    with monitor.session(monitor_dir, rank=host):
        monitor.progress(phase="compile")
        with monitor.span("bsp/compile"):
            model.compile_iter_fns(sync_type)

        ckpt = None
        start_epoch = 0
        if checkpoint:
            ckpt = Checkpointer(os.path.join(cfg.snapshot_dir, model.name))
            if resume:
                # integrity-checked resume (resilience.recovery): a
                # corrupt latest checkpoint falls back to the previous
                # kept epoch instead of killing the restart
                _, payload = ckpt.restore_latest_verified(like={
                    "state": model.state, "epoch": 0})
                if payload is not None:
                    # re-establish the model's sharding (a TP model would
                    # otherwise train on replicated restored arrays)
                    model.state = model.adopt_restored_state(
                        payload["state"])
                    start_epoch = int(payload["epoch"]) + 1
                    recorder.load(cfg.snapshot_dir)
                    # fast-forward the LR schedule (reference resume
                    # semantics)
                    model.adjust_hyperp(start_epoch)

        n_epochs = model.n_epochs if max_epochs is None else min(
            model.n_epochs, start_epoch + max_epochs)
        last_val: dict = {}
        with profiler:  # __exit__ stops the trace even on a crash
            try:
                for epoch in range(start_epoch, n_epochs):
                    # the epoch number rides the heartbeat (progress
                    # below) and this gauge, NOT a span label — a
                    # per-epoch label would shatter span_ms into one
                    # series per epoch
                    monitor.set_gauge("bsp/epoch", epoch)
                    with monitor.span("bsp/epoch"):
                        n_iters = model.begin_epoch(epoch)
                        it = 0
                        k = max(getattr(model.config, "steps_per_call", 1),
                                getattr(model.config, "grad_accum_steps", 1))
                        while it < n_iters:
                            # covers steps_per_call iterations per dispatch
                            t0 = time.monotonic()
                            consumed = model.train_iter(it, recorder)
                            if consumed is None:
                                # legacy override that returns nothing —
                                # only valid when each call consumes
                                # exactly one batch
                                if k > 1:
                                    raise RuntimeError(
                                        f"{type(model).__name__}.train_iter"
                                        " returned None with a stacked "
                                        "cadence (steps_per_call or "
                                        "grad_accum_steps > 1); it must "
                                        "return the number of iterations "
                                        "consumed")
                                consumed = 1
                            it += consumed
                            # per-iteration time (dispatch wall / iters
                            # covered); over a pipelined epoch the mean is
                            # honest because dispatch backpressure tracks
                            # device time
                            monitor.observe_step(
                                (time.monotonic() - t0) / consumed,
                                phase="train", step=it)
                            # trace spans epochs until n_steps hit,
                            # then waits for the device to have run them
                            profiler.step(fence=model.state.step)
                        model._flush_metrics(recorder)
                        monitor.progress(phase="validate")
                        with monitor.span("bsp/validate"):
                            last_val = model.val_epoch(recorder)
                            # times itself ('calc')
                        model.adjust_hyperp(epoch + 1)
                        if ckpt is not None:
                            monitor.progress(phase="checkpoint")
                            with monitor.span("bsp/checkpoint"):
                                ckpt.save(epoch, {"state": model.state,
                                                  "epoch": epoch})
                        recorder.epoch_summary(epoch, last_val.get("loss"),
                                               last_val.get("error"))
                        monitor.progress(phase="epoch_end", step=epoch)
            finally:
                model.cleanup()  # also on failure: stops the prefetcher
                if ckpt is not None:
                    ckpt.close()
    return {"val": last_val, "epochs_run": n_epochs - start_epoch,
            "records": recorder.epoch_records}


class BSP(Rule):
    """Synchronous BSP data-parallel rule (reference rule #1).

    ``model_parallel``/``seq_parallel`` carve those axes out of the
    device set (remaining devices go to ``data``) so tensor-parallel
    models (``transformer_lm_tp``) and sequence-parallel runs are
    reachable from the launcher, not just from Python."""

    name = "BSP"
    uses_global_mesh = True

    def _session(self, devs, modelfile, modelclass, config, resume,
                 sync_type, max_epochs=None, checkpoint=True,
                 model_parallel: int = 1, seq_parallel: int = 1,
                 pipe_parallel: int = 1, expert_parallel: int = 1,
                 monitor_dir: str | None = None,
                 **kwargs):
        if (model_parallel > 1 or seq_parallel > 1 or pipe_parallel > 1
                or expert_parallel > 1):
            from theanompi_tpu.parallel.mesh import (
                MeshSpec,
                make_training_mesh,
            )

            mesh = make_training_mesh(
                MeshSpec(data=-1, model=model_parallel, seq=seq_parallel,
                         pipe=pipe_parallel, expert=expert_parallel),
                devs)
        else:
            mesh = data_mesh(len(devs), devs)
        cls = resolve_model_class(modelfile, modelclass)
        self.model = cls(config=config, mesh=mesh, **kwargs)
        self.result = run_bsp_session(self.model, sync_type=sync_type,
                                      resume=resume, max_epochs=max_epochs,
                                      checkpoint=checkpoint,
                                      monitor_dir=monitor_dir)
