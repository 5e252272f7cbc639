"""Multi-host BSP: 2 controller processes x 4 virtual CPU devices form
ONE 8-device global mesh, and the loss curve matches the single-process
8-device run step for step.

This is the acceptance test for the reference's multi-node deployment
surface (``tmlauncher`` over mpirun — SURVEY.md §2.1/§3.1/§7-6; mount
empty, no file:line): psum crosses the process boundary (gloo on CPU,
DCN on real TPU pods), each host feeds only its slice of the global
batch (``jax.make_array_from_process_local_data``), and rank-0 gating
covers printing and the JSONL curve.

Runs real OS processes — the same discipline the reference needed a
cluster for, executable on one box.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import tempfile

import numpy as np
import pytest

RUNNER = os.path.join(os.path.dirname(__file__), "_multihost_runner.py")


REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _clean_env() -> dict:
    env = dict(os.environ)
    # the runner sets its own device-count flag; drop the conftest's
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_PLATFORMS", None)
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _run_procs(nprocs: int, port: int, outdir: str, devices_per_proc: int,
               epochs: int = 2, extra: list[str] | None = None) -> list[dict]:
    procs = []
    outs = []
    for pid in range(nprocs):
        out = os.path.join(outdir, f"out_{nprocs}p_{pid}.json")
        outs.append(out)
        cmd = [sys.executable, RUNNER, "--proc-id", str(pid),
               "--nprocs", str(nprocs), "--port", str(port),
               "--devices-per-proc", str(devices_per_proc),
               "--epochs", str(epochs), "--out", out,
               "--snapshot-dir", os.path.join(outdir, "snap")]
        procs.append(subprocess.Popen(cmd + (extra or []), env=_clean_env(),
                                      stdout=subprocess.PIPE,
                                      stderr=subprocess.STDOUT))
    results = []
    for p in procs:
        stdout, _ = p.communicate(timeout=600)
        assert p.returncode == 0, (
            f"runner failed (rc={p.returncode}):\n{stdout.decode()[-4000:]}")
    for out in outs:
        with open(out) as f:
            results.append(json.load(f))
    return results


@pytest.fixture(scope="module")
def workdir():
    d = tempfile.mkdtemp(prefix="tm_multihost_")
    yield d
    shutil.rmtree(d, ignore_errors=True)


@pytest.mark.slow
def test_two_process_bsp_matches_single_process(workdir):
    two = _run_procs(2, port=45711, outdir=workdir, devices_per_proc=4)
    one = _run_procs(1, port=45712, outdir=workdir, devices_per_proc=8)

    # both processes saw one global 8-device mesh, 4 local each
    for r in two:
        assert r["n_global_devices"] == 8
        assert r["n_local_devices"] == 4
        assert r["multiprocess"] is True
    assert one[0]["n_global_devices"] == 8
    assert one[0]["multiprocess"] is False

    # every process computes the same (replicated) loss sequence
    l0, l1 = np.array(two[0]["losses"]), np.array(two[1]["losses"])
    np.testing.assert_allclose(l0, l1, rtol=1e-6)

    # ... and it matches the single-process global-mesh run step for
    # step (same data order, same math; gloo vs single-process psum
    # reduction order can differ in the last ulp)
    single = np.array(one[0]["losses"])
    assert len(single) == len(l0) > 0
    np.testing.assert_allclose(l0, single, rtol=1e-4, atol=1e-6)

    # val path (host-sliced val batches + pmean) agrees too
    assert two[0]["val"]["error"] == pytest.approx(
        one[0]["val"]["error"], rel=1e-3, abs=1e-5)


@pytest.mark.slow
def test_two_process_checkpoint_resume(workdir):
    d = os.path.join(workdir, "resume")
    os.makedirs(d, exist_ok=True)
    # continuous 2-epoch reference
    cont = _run_procs(2, port=45713, outdir=d, devices_per_proc=4, epochs=2)
    # 1 epoch with checkpoint, then resume for 1 more
    d2 = os.path.join(workdir, "resume_split")
    os.makedirs(d2, exist_ok=True)
    _run_procs(2, port=45714, outdir=d2, devices_per_proc=4, epochs=1,
               extra=["--checkpoint"])
    resumed = _run_procs(2, port=45715, outdir=d2, devices_per_proc=4,
                         epochs=1, extra=["--checkpoint", "--resume"])

    assert resumed[0]["epochs_run"] == 1
    n = len(cont[0]["losses"]) // 2
    np.testing.assert_allclose(np.array(resumed[0]["losses"]),
                               np.array(cont[0]["losses"])[n:],
                               rtol=1e-4, atol=1e-6)


@pytest.mark.slow
def test_two_process_zero_sharding_matches_plain(workdir):
    """ZeRO-1 across the process boundary: psum_scatter + all_gather
    ride the gloo/DCN collectives, the sharded optimizer state spans
    both processes' devices, and the loss curve matches plain BSP."""
    zero = _run_procs(2, port=45717, outdir=workdir, devices_per_proc=4,
                      epochs=1, extra=["--zero"])
    plain = _run_procs(2, port=45718, outdir=workdir, devices_per_proc=4,
                       epochs=1)
    lz = np.array(zero[0]["losses"])
    lp = np.array(plain[0]["losses"])
    assert len(lz) == len(lp) > 0
    # elementwise-optimizer ZeRO is step-equal to plain BSP
    np.testing.assert_allclose(lz, lp, rtol=1e-4, atol=1e-6)
    # both ranks agree with each other
    np.testing.assert_allclose(lz, np.array(zero[1]["losses"]), rtol=1e-6)


@pytest.mark.slow
def test_tmlauncher_cli_two_processes(workdir):
    """The actual ``tmlauncher`` CLI as real OS processes (VERDICT r2
    #3): argv → --platform ordering → jax.distributed.initialize →
    global mesh → session.  Two hosts × 4 devices must produce the
    same epoch record as one 8-device host running the same command —
    covering the one seam (launcher.py ``_run``) the runner-based
    multihost tests bypass."""
    d = os.path.join(workdir, "cli")
    os.makedirs(d, exist_ok=True)

    def run_cli(nhosts, host_id, port, devices, snap):
        env = _clean_env()
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={devices}")
        cmd = [sys.executable, "-m", "theanompi_tpu.launcher",
               "--multihost", "BSP", "-m", "tests._tiny_models",
               "-c", "TinyCifar", "--platform", "cpu",
               "--epochs", "1", "--batch-size", "16", "--lr", "0.02",
               "--snapshot-dir", snap,
               "--coordinator", f"127.0.0.1:{port}",
               "--nhosts", str(nhosts), "--host-id", str(host_id)]
        return subprocess.Popen(cmd, env=env, cwd=REPO_ROOT,
                                stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT)

    snap2, snap1 = os.path.join(d, "snap2"), os.path.join(d, "snap1")
    procs = [run_cli(2, i, 45727, 4, snap2) for i in range(2)]
    try:
        for p in procs:
            stdout, _ = p.communicate(timeout=600)
            assert p.returncode == 0, (
                f"tmlauncher failed (rc={p.returncode}):\n"
                f"{stdout.decode()[-4000:]}")
            assert "final val:" in stdout.decode()
    finally:
        for p in procs:  # a failed host-0 assert must not orphan host 1
            if p.poll() is None:
                p.kill()
                p.wait(30)
    p1 = run_cli(1, 0, 45728, 8, snap1)
    out1, _ = p1.communicate(timeout=600)
    assert p1.returncode == 0, out1.decode()[-4000:]

    def epoch_rec(snap, rank):
        with open(os.path.join(snap, f"record_rank{rank}.jsonl")) as f:
            return [json.loads(line) for line in f if line.strip()][-1]

    two, one = epoch_rec(snap2, 0), epoch_rec(snap1, 0)
    assert two["train_loss"] == pytest.approx(one["train_loss"], rel=1e-4)
    assert two["val_error"] == pytest.approx(one["val_error"],
                                             rel=1e-3, abs=1e-5)
    # rank-0 gating (SURVEY §3.5): ONLY host 0 writes the JSONL curve
    assert not os.path.exists(
        os.path.join(snap2, "record_rank1.jsonl"))


@pytest.mark.slow
def test_two_process_async_save_survives_donation(workdir):
    """The async-save/donation seam (ADVICE r2): save() returns while
    Orbax writes in the background, and the very next train step
    DONATES the saved state's device buffers.  Each process saves its
    cross-process-sharded ZeRO state, immediately donates, restores,
    and asserts bit-equal pre-save values — so the Orbax contract
    (d2h copy completes before save() returns) is tested, not assumed."""
    d = os.path.join(workdir, "donate_race")
    os.makedirs(d, exist_ok=True)
    res = _run_procs(2, port=45725, outdir=d, devices_per_proc=4,
                     extra=["--donate-race"])
    for r in res:
        assert r["donate_race_ok"] is True
        assert r["state_spans_processes"] is True


@pytest.mark.slow
def test_two_process_zero_checkpoint_resume(workdir):
    """Checkpointing a cross-process-SHARDED optimizer state: Orbax
    writes each process's addressable shards (no single host can fetch
    the whole array), and resume restores into the same sharding.
    1 epoch + checkpoint, then 1 more from resume == 2 continuous."""
    d = os.path.join(workdir, "zero_resume")
    os.makedirs(d, exist_ok=True)
    cont = _run_procs(2, port=45721, outdir=d, devices_per_proc=4,
                      epochs=2, extra=["--zero", "--checkpoint"])
    d2 = os.path.join(workdir, "zero_resume2")
    os.makedirs(d2, exist_ok=True)
    first = _run_procs(2, port=45722, outdir=d2, devices_per_proc=4,
                       epochs=1, extra=["--zero", "--checkpoint"])
    second = _run_procs(2, port=45723, outdir=d2, devices_per_proc=4,
                        epochs=1, extra=["--zero", "--checkpoint",
                                         "--resume"])
    # the resumed epoch-2 losses equal the continuous run's epoch 2
    lc = np.array(cont[0]["losses"])
    l1 = np.array(first[0]["losses"])
    l2 = np.array(second[0]["losses"])
    n = len(l1)
    np.testing.assert_allclose(l1, lc[:n], rtol=1e-6)
    np.testing.assert_allclose(l2, lc[n:n + len(l2)], rtol=1e-5,
                               atol=1e-7)


@pytest.mark.slow
def test_two_process_fsdp_matches_plain(workdir):
    """FSDP across the process boundary: params + optimizer state live
    1/8 per device SPANNING both processes, GSPMD's gathers and
    reduce-scatters ride the gloo/DCN collectives, and the trajectory
    is step-equal to plain BSP."""
    fsdp = _run_procs(2, port=45727, outdir=workdir, devices_per_proc=4,
                      epochs=1, extra=["--fsdp"])
    plain = _run_procs(2, port=45728, outdir=workdir, devices_per_proc=4,
                       epochs=1)
    lf = np.array(fsdp[0]["losses"])
    lp = np.array(plain[0]["losses"])
    assert len(lf) == len(lp) > 0
    np.testing.assert_allclose(lf, lp, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(lf, np.array(fsdp[1]["losses"]), rtol=1e-6)
    assert fsdp[0]["val"]["error"] == pytest.approx(
        plain[0]["val"]["error"], rel=1e-3, abs=1e-5)
