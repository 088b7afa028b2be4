"""The north-star configuration as a test: .rec -> native JPEG decode ->
ImageRecordIter augment -> SPMDTrainer compiled step (reference:
example/image-classification/train_imagenet.py)."""
import os
import subprocess
import sys

import pytest


def test_train_imagenet_rec_example_runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(__file__), "..", "examples",
                      "train_imagenet_rec.py"),
         "--images", "64", "--batch", "8", "--image-size", "32",
         "--depth", "18", "--steps", "3", "--threads", "2"],
        env=env, capture_output=True, text=True, timeout=540)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "pipeline" in out.stdout and "img/s" in out.stdout, out.stdout


def test_train_gan_toy_example_converges():
    """Adversarial two-Trainer pattern (reference example/gluon/dcgan):
    the generator must move its mass from the origin toward the ring."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(__file__), "..", "examples",
                      "train_gan_toy.py"), "--steps", "150"],
        env=env, capture_output=True, text=True, timeout=540)
    assert out.returncode == 0, out.stderr[-2000:]
    import re

    m = re.search(r"mean radius ([0-9.]+)", out.stdout)
    assert m, out.stdout
    assert 0.8 < float(m.group(1)) < 3.5, out.stdout


def test_device_prefetch_iter_overlap(tmp_path):
    """DevicePrefetchIter stages batches to the device off-thread and
    preserves order/content; reset restarts the stream."""
    import numpy as onp

    from mxnet_tpu import io as mxio, nd

    X = onp.arange(8 * 4, dtype="f").reshape(8, 4)
    Y = onp.arange(8, dtype="f")
    base = mxio.NDArrayIter(nd.array(X), nd.array(Y), batch_size=4)
    pf = mxio.DevicePrefetchIter(base)
    b1 = next(pf)
    b2 = next(pf)
    onp.testing.assert_allclose(b1.data[0].asnumpy(), X[:4])
    onp.testing.assert_allclose(b2.data[0].asnumpy(), X[4:])
    try:
        next(pf)
        assert False, "expected StopIteration"
    except StopIteration:
        pass
    pf.reset()
    again = [b.data[0].asnumpy() for b in pf]
    assert len(again) == 2
    onp.testing.assert_allclose(again[0], X[:4])


@pytest.mark.slow  # same example as the _runs test above, +overlap JSON
def test_train_imagenet_rec_overlap_report(tmp_path):
    import json
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=repo, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "examples",
                                      "train_imagenet_rec.py"),
         "--images", "96", "--batch", "16", "--image-size", "32",
         "--depth", "18", "--steps", "3", "--overlap-report"],
        env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-1500:]
    line = [l for l in r.stdout.splitlines()
            if l.startswith("{") and "data_fed" in l]
    assert line, r.stdout
    payload = json.loads(line[-1])
    assert payload["extra"]["overlap_efficiency_pct"] > 30


def test_recommender_mf_example_converges():
    """examples/train_recommender_mf.py: two-Embedding dot-product MF
    (reference example/recommenders) converges on synthetic ratings."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=repo, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable,
         os.path.join(repo, "examples", "train_recommender_mf.py"),
         "--epochs", "10", "--ratings", "2000"],
        env=env, capture_output=True, text=True, timeout=500)
    assert r.returncode == 0, (r.stdout[-500:], r.stderr[-500:])
    assert "->" in r.stdout
