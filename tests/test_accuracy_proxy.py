"""Accuracy-parity proxy (VERDICT r4 item 9, zero-egress variant):
train on sklearn's REAL digits dataset through the full gluon stack and
match the published classical baseline (~97%). The committed artifact is
ACCURACY_r05.json (examples/train_digits_accuracy.py)."""
import os
import subprocess
import sys


def test_digits_accuracy_beats_published_baseline(tmp_path):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=repo, JAX_PLATFORMS="cpu")
    out = tmp_path / "acc.json"
    r = subprocess.run(
        [sys.executable,
         os.path.join(repo, "examples", "train_digits_accuracy.py"),
         "--json", str(out), "--epochs", "30"],
        env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, (r.stdout[-800:], r.stderr[-800:])
    import json

    payload = json.loads(out.read_text())
    assert payload["value"] >= 0.97, payload
