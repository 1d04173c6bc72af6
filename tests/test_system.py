"""End-to-end behaviour tests: the full training driver (with failure
injection + elastic resume) and the serving driver, on CPU."""
import numpy as np
import pytest

from repro.launch.serve import main as serve_main
from repro.launch.train import main as train_main


def test_train_loss_improves(tmp_path):
    losses = train_main([
        "--steps", "14", "--ckpt-every", "7", "--quiet",
        "--ckpt-dir", str(tmp_path / "ck"), "--global-batch", "8",
        "--seq", "64",
    ])["loss"]
    assert np.mean(losses[-4:]) < np.mean(losses[:4])


def test_train_resume_from_checkpoint(tmp_path):
    ck = str(tmp_path / "ck")
    train_main(["--steps", "8", "--ckpt-every", "4", "--quiet",
                "--ckpt-dir", ck, "--global-batch", "8", "--seq", "64"])
    losses = train_main(["--steps", "12", "--ckpt-every", "4", "--quiet",
                         "--resume", "--ckpt-dir", ck,
                         "--global-batch", "8", "--seq", "64"])["loss"]
    assert len(losses) == 4                     # resumed at 8, ran to 12


def test_failure_injection_recovers(tmp_path):
    losses = train_main([
        "--steps", "12", "--ckpt-every", "4", "--inject-failure", "6",
        "--quiet", "--ckpt-dir", str(tmp_path / "ck"),
        "--global-batch", "8", "--seq", "64",
    ])["loss"]
    # restored to step 4 then re-ran: more recorded steps than 12
    assert len(losses) >= 12
    assert np.mean(losses[-4:]) < np.mean(losses[:4])


@pytest.mark.parametrize("arch", ["yi-6b", "mamba2-370m", "musicgen-large"])
def test_serve_generates(arch):
    out = serve_main(["--arch", arch, "--batch", "2", "--prompt-len", "16",
                      "--gen-len", "8"])
    assert out.shape == (2, 8)
