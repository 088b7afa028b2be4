"""Launcher, bandwidth tool, contrib.text, tensorboard writer, legacy
mx.rnn cells + BucketSentenceIter, env-knob registry.

Reference coverage model: tests/python/unittest/test_contrib_text.py,
test_rnn.py, plus tracker smoke tests under tools/.
"""
import collections
import os
import struct
import subprocess
import sys

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, rnn, env
import mxnet_tpu.symbol as sym

rs = onp.random.RandomState(4)


# ------------------------------------------------------------- launcher ---

def test_launch_local_spawns_workers(tmp_path):
    from mxnet_tpu.tools import launch

    out = tmp_path / "out"
    script = tmp_path / "w.py"
    script.write_text(
        "import os\n"
        f"open(r'{out}' + os.environ['MXNET_PROCESS_ID'], 'w')"
        ".write(os.environ['MXNET_NUM_PROCESSES'] + ' ' +"
        "os.environ['MXNET_COORDINATOR'])\n")
    rc = launch.main(["-n", "3", "--launcher", "local",
                      "--env", "FOO:bar",
                      sys.executable, str(script)])
    assert rc == 0
    for rank in range(3):
        text = (tmp_path / f"out{rank}").read_text()
        assert text.startswith("3 127.0.0.1:")


def test_launch_init_noop_without_env(monkeypatch):
    from mxnet_tpu.tools import launch

    monkeypatch.delenv("MXNET_COORDINATOR", raising=False)
    assert launch.init() is False


def test_bandwidth_tool_runs():
    from mxnet_tpu.tools import bandwidth

    res = bandwidth.measure(4096, iters=2, warmup=1)
    assert res["num_devices"] >= 1
    assert res["collective_gbps"] > 0
    assert res["kvstore_gbps"] > 0


# ----------------------------------------------------------- contrib.text ---

def test_vocabulary():
    from mxnet_tpu.contrib import text

    counter = text.utils.count_tokens_from_str(
        "a b b c c c\nd d d d", to_lower=False)
    assert counter == collections.Counter(
        {"d": 4, "c": 3, "b": 2, "a": 1})
    v = text.Vocabulary(counter, min_freq=2,
                        reserved_tokens=["<pad>"])
    assert v.idx_to_token[:2] == ["<unk>", "<pad>"]
    assert v.to_indices("d") == 2  # most frequent first
    assert v.to_indices(["c", "zzz"]) == [3, 0]  # unknown -> 0
    assert v.to_tokens(2) == "d"
    assert len(v) == 5  # unk, pad, d, c, b


def test_custom_embedding(tmp_path):
    from mxnet_tpu.contrib.text import embedding

    f = tmp_path / "emb.txt"
    f.write_text("hello 1.0 2.0 3.0\nworld 4.0 5.0 6.0\n")
    emb = embedding.CustomEmbedding(str(f))
    assert emb.vec_len == 3
    vec = emb.get_vecs_by_tokens("world")
    onp.testing.assert_allclose(vec.asnumpy(), [4, 5, 6])
    vecs = emb.get_vecs_by_tokens(["hello", "nope"])
    onp.testing.assert_allclose(vecs.asnumpy()[0], [1, 2, 3])
    onp.testing.assert_allclose(vecs.asnumpy()[1], [0, 0, 0])
    emb.update_token_vectors("hello", nd.array([[9.0, 9.0, 9.0]]))
    onp.testing.assert_allclose(
        emb.get_vecs_by_tokens("hello").asnumpy(), [9, 9, 9])
    # registry
    assert "customembedding" in embedding.get_pretrained_file_names()


# ------------------------------------------------------------ tensorboard ---

def test_tensorboard_event_file(tmp_path):
    from mxnet_tpu.contrib.tensorboard import SummaryWriter

    w = SummaryWriter(str(tmp_path))
    w.add_scalar("loss", 0.5, global_step=1)
    w.add_scalar("loss", 0.25, global_step=2)
    w.close()
    files = [f for f in os.listdir(tmp_path) if "tfevents" in f]
    assert len(files) == 1
    # verify tfrecord framing: length + masked crc of length header
    from mxnet_tpu.contrib.tensorboard import _masked_crc

    with open(tmp_path / files[0], "rb") as f:
        blob = f.read()
    off = 0
    events = 0
    while off < len(blob):
        (ln,) = struct.unpack_from("<Q", blob, off)
        (crc,) = struct.unpack_from("<I", blob, off + 8)
        assert crc == _masked_crc(blob[off:off + 8])
        payload = blob[off + 12:off + 12 + ln]
        (pcrc,) = struct.unpack_from("<I", blob, off + 12 + ln)
        assert pcrc == _masked_crc(payload)
        off += 12 + ln + 4
        events += 1
    assert events == 3  # file-version event + 2 scalars
    assert b"loss" in blob


# ------------------------------------------------------------- legacy rnn ---

def _run_unrolled(cell, T=4, N=2, C=3, H=5):
    outputs, states = cell.unroll(T, sym.Variable("data"),
                                  merge_outputs=True)
    feed = {"data": nd.array(rs.randn(N, T, C).astype("f"))}
    args = outputs.list_arguments()
    shapes = {"data": (N, T, C)}
    for name in args:
        if name == "data":
            continue
        if "i2h_weight" in name:
            feed[name] = nd.array(rs.randn(
                H * _gates(cell), C).astype("f") * 0.1)
        elif "h2h_weight" in name:
            feed[name] = nd.array(rs.randn(
                H * _gates(cell), H).astype("f") * 0.1)
        elif "bias" in name:
            feed[name] = nd.zeros((H * _gates(cell),))
        elif "begin_state" in name:
            feed[name] = nd.zeros((N, H))
    ex = outputs.bind(mx.cpu(), feed)
    (out,) = ex.forward()
    return out


def _gates(cell):
    from mxnet_tpu.rnn import LSTMCell, GRUCell

    if isinstance(cell, LSTMCell):
        return 4
    if isinstance(cell, GRUCell):
        return 3
    return 1


@pytest.mark.parametrize("ctor", [rnn.RNNCell, rnn.LSTMCell,
                                  rnn.GRUCell])
def test_legacy_cell_unroll_shapes(ctor):
    out = _run_unrolled(ctor(5))
    assert out.shape == (2, 4, 5)
    assert onp.isfinite(out.asnumpy()).all()


def test_legacy_lstm_matches_gluon():
    """The symbolic LSTMCell unroll and the gluon LSTM agree given the
    same weights."""
    from mxnet_tpu.gluon import rnn as grnn

    T, N, C, H = 3, 2, 4, 5
    x = rs.randn(N, T, C).astype("f")
    iW = rs.randn(4 * H, C).astype("f") * 0.2
    hW = rs.randn(4 * H, H).astype("f") * 0.2
    iB = rs.randn(4 * H).astype("f") * 0.1
    hB = rs.randn(4 * H).astype("f") * 0.1

    cell = rnn.LSTMCell(H, prefix="l_")
    outputs, _ = cell.unroll(T, sym.Variable("data"),
                             merge_outputs=True)
    ex = outputs.bind(mx.cpu(), {
        "data": nd.array(x), "l_i2h_weight": nd.array(iW),
        "l_h2h_weight": nd.array(hW), "l_i2h_bias": nd.array(iB),
        "l_h2h_bias": nd.array(hB),
        "l_begin_state_0": nd.zeros((N, H)),
        "l_begin_state_1": nd.zeros((N, H))})
    (out_sym,) = ex.forward()

    layer = grnn.LSTM(H, layout="NTC", input_size=C)
    layer.initialize()
    params = {p.name: p for p in layer.collect_params().values()}
    for name, p in params.items():
        if "i2h_weight" in name:
            p.set_data(nd.array(iW))
        elif "h2h_weight" in name:
            p.set_data(nd.array(hW))
        elif "i2h_bias" in name:
            p.set_data(nd.array(iB))
        elif "h2h_bias" in name:
            p.set_data(nd.array(hB))
    out_gluon = layer(nd.array(x))
    onp.testing.assert_allclose(out_sym.asnumpy(), out_gluon.asnumpy(),
                                rtol=2e-3, atol=1e-4)


def test_sequential_and_fused_cells():
    stack = rnn.SequentialRNNCell()
    stack.add(rnn.LSTMCell(4, prefix="a_"))
    stack.add(rnn.DropoutCell(0.0))
    stack.add(rnn.GRUCell(4, prefix="b_"))
    outputs, states = stack.unroll(3, sym.Variable("data"),
                                   merge_outputs=True)
    assert len(states) == 3  # lstm h,c + gru h
    fused = rnn.FusedRNNCell(4, num_layers=2, mode="lstm")
    unf = fused.unfuse()
    assert len(unf._cells) == 2


def test_encode_sentences_and_bucket_iter():
    sentences = [["a", "b", "c"], ["a", "c"], ["b", "c", "a", "d"],
                 ["a", "b"], ["c", "a"], ["d", "c", "a"]]
    coded, vocab = rnn.encode_sentences(sentences, invalid_label=0,
                                        start_label=1)
    assert all(all(i >= 1 for i in s) for s in coded)
    it = rnn.BucketSentenceIter(coded, batch_size=2, buckets=[2, 3, 4],
                                invalid_label=0)
    seen = 0
    for batch in it:
        T = batch.bucket_key
        assert batch.data[0].shape == (2, T)
        assert batch.label[0].shape == (2, T)
        d = batch.data[0].asnumpy()
        lab = batch.label[0].asnumpy()
        # label is data shifted left
        onp.testing.assert_allclose(lab[:, :-1], d[:, 1:])
        seen += 1
    # bucket 2 holds 3 sentences (1 batch), bucket 3 holds 2 (1 batch),
    # bucket 4 holds 1 (< batch_size, dropped)
    assert seen == 2


# ------------------------------------------------------------- env knobs ---

def test_env_registry():
    assert "MXNET_ENGINE_TYPE" in env.KNOBS
    table = env.describe()
    assert "MXNET_KVSTORE_BIGARRAY_BOUND" in table
    assert env.get_int("MXNET_NOT_SET_XYZ", 7) == 7


def test_env_check_warns_on_unknown(monkeypatch, caplog):
    monkeypatch.setenv("MXNET_TOTALLY_BOGUS_KNOB", "1")
    unknown = env.check()
    assert "MXNET_TOTALLY_BOGUS_KNOB" in unknown


def test_env_kvstore_gc(monkeypatch):
    from mxnet_tpu import kvstore

    monkeypatch.setenv("MXNET_KVSTORE_GC_TYPE", "2bit")
    monkeypatch.setenv("MXNET_KVSTORE_GC_THRESHOLD", "0.25")
    kv = kvstore.create("device")
    assert kv._compression is not None
    assert kv._compression.threshold == 0.25


def test_mxnet_seed_subprocess(tmp_path):
    script = tmp_path / "s.py"
    script.write_text(
        "import os\n"
        "import sys; sys.path.insert(0, r'%s')\n"
        "import mxnet_tpu as mx\n"
        "from mxnet_tpu import nd\n"
        "print(float(mx.nd.random.uniform(shape=(1,)).asnumpy()[0]))\n"
        % os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    env_base = dict(os.environ, MXNET_SEED="42", JAX_PLATFORMS="cpu")
    r1 = subprocess.run([sys.executable, str(script)], env=env_base,
                        capture_output=True, text=True, timeout=300)
    r2 = subprocess.run([sys.executable, str(script)], env=env_base,
                        capture_output=True, text=True, timeout=300)
    assert r1.returncode == 0, r1.stderr[-500:]
    assert r1.stdout.strip() == r2.stdout.strip()
