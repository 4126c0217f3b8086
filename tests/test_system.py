"""End-to-end behaviour: training learns, checkpoint-resume is exact,
serving generates, the DIMA path serves, dry-run cells lower+compile."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_training_learns(tmp_path):
    from repro.launch.train import main
    losses = main(["--arch", "gemma3-1b", "--reduced", "--steps", "60",
                   "--batch", "8", "--seq", "64", "--no-mesh",
                   "--log-every", "100"])
    first = np.mean(losses[:5])
    last = np.mean(losses[-5:])
    assert last < first - 0.5, (first, last)


def test_resume_is_exact(tmp_path):
    """Train 20 steps with a checkpoint at 10; resume from 10 and verify
    the loss trajectory matches the uninterrupted run (stateless data +
    exact state restore)."""
    from repro.launch.train import main
    d1 = str(tmp_path / "a")
    d2 = str(tmp_path / "b")
    full = main(["--arch", "gemma3-1b", "--reduced", "--steps", "20",
                 "--batch", "4", "--seq", "32", "--no-mesh",
                 "--ckpt-dir", d2, "--log-every", "100"])
    main(["--arch", "gemma3-1b", "--reduced", "--steps", "20",
          "--stop-at", "10", "--batch", "4", "--seq", "32", "--no-mesh",
          "--ckpt-dir", d1, "--log-every", "100"])
    resumed = main(["--arch", "gemma3-1b", "--reduced", "--steps", "20",
                    "--batch", "4", "--seq", "32", "--no-mesh",
                    "--ckpt-dir", d1, "--resume", "--log-every", "100"])
    np.testing.assert_allclose(np.asarray(full[10:]), np.asarray(resumed),
                               rtol=2e-4, atol=2e-4)


def test_serve_generates():
    from repro.launch.serve import main
    out = main(["--arch", "musicgen-large", "--reduced", "--batch", "2",
                "--prompt-len", "8", "--gen", "4"])
    assert out.shape == (2, 4)


def test_serve_dima_quant():
    from repro.launch.serve import main
    out = main(["--arch", "gemma3-1b", "--reduced", "--batch", "2",
                "--prompt-len", "8", "--gen", "4", "--quant", "dima",
                "--dima-noise"])
    assert out.shape == (2, 4)


@pytest.mark.slow
def test_dryrun_cell_compiles(tmp_path):
    """One full-size dry-run cell end-to-end in a subprocess (512 devices)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "gemma3-1b",
         "--shape", "decode_32k", "--multi-pod", "--force",
         "--out-dir", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=560, cwd=ROOT)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "OK" in out.stdout
    rec = json.loads((tmp_path / "gemma3-1b__decode_32k__pod2x16x16.json")
                     .read_text())
    assert rec["ok"], rec.get("error")


def test_dryrun_results_all_ok():
    """The committed dry-run sweep must be green for every cell x mesh."""
    d = os.path.join(ROOT, "experiments", "dryrun")
    files = ([f for f in os.listdir(d) if f.endswith(".json")
              and "__" in f and "opt" not in f]
             if os.path.isdir(d) else [])
    if len(files) < 66:
        pytest.skip(f"dry-run sweep not (fully) generated: {len(files)} "
                    "cells on disk (python -m repro.launch.dryrun --all)")
    from repro.configs import cells
    want = set()
    for a, s in cells():
        want.add((a, s, "pod16x16"))
        want.add((a, s, "pod2x16x16"))
    seen = set()
    for f in files:
        rec = json.load(open(os.path.join(d, f)))
        if (rec["arch"], rec["shape"], rec["mesh"]) in want:
            assert rec["ok"], (f, rec.get("error"))
            seen.add((rec["arch"], rec["shape"], rec["mesh"]))
    assert seen == want, want - seen
