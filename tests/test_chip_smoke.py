"""chip_smoke.py's checks, driven at a tiny size on the 8-device CPU
mesh — including the legs that must make it FAIL.  The script's
full-size path runs on a TPU only (tools: ``python chip_smoke.py``
through the chip tool); what is pinned here is that its checks hold on
a healthy run and refuse an unhealthy one."""

from __future__ import annotations

import math
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402
from chip_smoke import SmokeFailure  # noqa: E402


@pytest.fixture(scope="module")
def tiny_run(tmp_path_factory):
    """ONE tiny trainer leg (TinyCifar, 512 images, 8 per device on the
    8-device mesh -> 8 steps) shared by every test that inspects it."""
    out = str(tmp_path_factory.mktemp("smoke"))
    report = chip_smoke.trainer_leg(
        out, "tests._tiny_models", "cifar10", 512, 8,
        ["-c", "TinyCifar", "--batch-size", "8"])
    return out, report


class TestTrainerLeg:
    def test_healthy_run_passes_every_check(self, tiny_run):
        out, rep = tiny_run
        assert rep["steps"] == 8
        assert math.isfinite(rep["train_loss_first"])
        assert math.isfinite(rep["train_loss_last"])
        assert math.isfinite(rep["val_loss"])
        assert rep["first_step_s"] > 0 and rep["rest_s"] >= 0
        # images and labels each sit on all 8 devices
        assert [len(set(ids)) for ids in rep["batch_devices"]] == [8, 8]
        # everything it wrote is under the one directory it was given
        assert sorted(os.listdir(out)) == ["result.json", "snapshots"]

    def test_checkpoint_restores_at_the_step_reached(self, tiny_run):
        out, rep = tiny_run
        snaps = os.path.join(out, "snapshots")
        chip_smoke.check_checkpoint_restores(snaps, "cifar10", 8)
        with pytest.raises(SmokeFailure, match="holds step 8"):
            chip_smoke.check_checkpoint_restores(snaps, "cifar10", 9)

    def test_missing_checkpoint_fails(self, tmp_path):
        with pytest.raises(SmokeFailure, match="no checkpoint dir"):
            chip_smoke.check_checkpoint_restores(str(tmp_path), "nope", 1)

    def test_corrupt_checkpoint_fails(self, tiny_run, tmp_path):
        """A checkpoint that no longer matches its manifest does not
        'restore' — the smoke must say so, not pass on file presence."""
        out, _ = tiny_run
        snaps = str(tmp_path / "snapshots")
        shutil.copytree(os.path.join(out, "snapshots"), snaps)
        from theanompi_tpu.utils.checkpoint import _truncate_largest_file

        _truncate_largest_file(os.path.join(snaps, "cifar10", "0"))
        with pytest.raises(SmokeFailure, match="no restorable"):
            chip_smoke.check_checkpoint_restores(snaps, "cifar10", 8)


class TestCheckFailureLegs:
    def test_non_finite_train_loss_fails(self):
        chip_smoke.check_losses([2.3, 2.1], 2.2)
        with pytest.raises(SmokeFailure, match=r"step\(s\) \[1\]"):
            chip_smoke.check_losses([2.3, float("nan"), 2.1], 2.2)
        with pytest.raises(SmokeFailure, match="non-finite train"):
            chip_smoke.check_losses([float("inf")], 2.2)

    def test_non_finite_or_absent_val_loss_fails(self):
        with pytest.raises(SmokeFailure, match="val loss"):
            chip_smoke.check_losses([2.3], float("nan"))
        with pytest.raises(SmokeFailure, match="val loss"):
            chip_smoke.check_losses([2.3], None)

    def test_no_recorded_loss_fails(self):
        with pytest.raises(SmokeFailure, match="no train loss"):
            chip_smoke.check_losses([], 2.2)

    def test_wrong_step_count_fails(self):
        chip_smoke.check_step_count(64, 1, 8192, 128)
        chip_smoke.check_step_count(16, 4, 8192, 128)
        with pytest.raises(SmokeFailure, match="expected 8192"):
            chip_smoke.check_step_count(64, 4, 8192, 128)
        with pytest.raises(SmokeFailure, match="= 64"):
            chip_smoke.check_step_count(63, 1, 8192, 128)

    def test_batch_on_one_device_only_fails(self):
        chip_smoke.check_batch_placement([[0, 1, 2, 3], [0, 1, 2, 3]], 4)
        with pytest.raises(SmokeFailure, match=r"device\(s\) \[0\]"):
            chip_smoke.check_batch_placement([[0, 0, 0, 0], [0]], 4)
        # one leaf sharded, the other not: still a failure
        with pytest.raises(SmokeFailure, match="leaf 1"):
            chip_smoke.check_batch_placement([[0, 1, 2, 3], [0]], 4)
        with pytest.raises(SmokeFailure, match="no staged batch"):
            chip_smoke.check_batch_placement(None, 4)

    def test_idle_device_fails(self):
        chip_smoke.check_peak_memory({"TPU_0": 1 << 20, "TPU_1": 5})
        with pytest.raises(SmokeFailure, match="TPU_1"):
            chip_smoke.check_peak_memory({"TPU_0": 1 << 20, "TPU_1": 0})
        with pytest.raises(SmokeFailure, match="TPU_3"):
            chip_smoke.check_peak_memory({"TPU_0": 9, "TPU_3": None})

    def test_trainer_error_code_fails(self, monkeypatch):
        import theanompi_tpu.launcher as launcher

        monkeypatch.setattr(launcher, "tmlocal", lambda argv: 3)
        with pytest.raises(SmokeFailure, match="returned 3"):
            chip_smoke.run_trainer(["BSP"])


_TINY_CASES = chip_smoke.kernel_cases(full=False)


class TestKernelLeg:
    @pytest.mark.parametrize("case", _TINY_CASES,
                             ids=[c.name for c in _TINY_CASES])
    def test_tiny_case_matches_xla(self, case):
        """Interpret mode on the CPU: same comparison code as the chip
        run, a few tiles big, no Mosaic call required."""
        rep = chip_smoke.run_kernel_case(case, require_mosaic=False)
        assert rep["mosaic_calls"] == 0  # interpret mode lowers inline
        assert rep["worst_error_over_tolerance"] <= 1.0
        assert rep["elements_over_tolerance"] == 0

    def test_every_ops_kernel_has_a_case(self):
        """A kernel added to theanompi_tpu/ops without a case here
        would ship uncompiled — the state this leg exists to end."""
        import glob

        import theanompi_tpu.ops as ops

        with_kernel = set()
        for path in glob.glob(os.path.join(
                os.path.dirname(ops.__file__), "*.py")):
            with open(path) as f:
                if "pl.pallas_call(" in f.read():
                    with_kernel.add(os.path.basename(path))
        assert with_kernel == {"lrn_pallas.py", "attention.py",
                               "fused_bn.py", "grouped_matmul.py", "ssd.py",
                               "gated_delta.py", "expert_rows.py"}
        names = " ".join(c.name for c in _TINY_CASES)
        for stem in ("lrn", "attention", "attention_gqa",
                     "attention_window", "fused_bn",
                     "grouped_matmul", "ssd", "gated_delta", "expert_rows"):
            assert stem in names

    def test_kernel_that_disagrees_fails(self):
        import dataclasses

        case = _TINY_CASES[0]
        good = case.fn

        def skewed(impl):
            f = good(impl)
            return (lambda *a: f(*a) * 1.5) if impl == "pallas" else f

        with pytest.raises(SmokeFailure, match="differ from the XLA"):
            chip_smoke.run_kernel_case(
                dataclasses.replace(case, fn=skewed), require_mosaic=False)

    def test_missing_mosaic_call_fails(self):
        """On the chip an interpreted (or silently XLA) 'kernel' must
        not pass as compiled: zero tpu_custom_call -> failure."""
        with pytest.raises(SmokeFailure, match="0 Mosaic call"):
            chip_smoke.run_kernel_case(_TINY_CASES[0], require_mosaic=True)

    def test_leg_names_every_failing_kernel(self, monkeypatch):
        import dataclasses

        def refuse(impl):
            def f(*a):
                raise NotImplementedError("mosaic says no")
            return f if impl == "pallas" else _TINY_CASES[0].fn(impl)

        cases = [dataclasses.replace(_TINY_CASES[0], name="k_refused",
                                     fn=refuse), _TINY_CASES[2]]
        monkeypatch.setattr(chip_smoke, "kernel_cases", lambda full: cases)
        with pytest.raises(SmokeFailure) as ei:
            chip_smoke.kernel_leg(full=False, require_mosaic=False)
        assert "k_refused: NotImplementedError: mosaic says no" in str(
            ei.value)
        assert _TINY_CASES[2].name not in str(ei.value)  # it passed


class TestGate:
    def test_no_accelerator_exits_non_zero_in_one_line(self, capsys,
                                                       tmp_path):
        rc = chip_smoke.main(["chip_smoke.py", str(tmp_path / "out")])
        cap = capsys.readouterr()
        assert rc != 0
        assert cap.out == ""  # no result line
        assert len(cap.err.strip().splitlines()) == 1
        assert "no accelerator" in cap.err
        assert not (tmp_path / "out").exists()  # nothing ran

    def test_alone_in_a_directory_it_fails_without_a_result(self, tmp_path):
        shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
        env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
        r = subprocess.run([sys.executable, "chip_smoke.py"], env=env,
                           cwd=str(tmp_path), capture_output=True,
                           text=True, timeout=120)
        assert r.returncode != 0
        assert r.stdout == ""
