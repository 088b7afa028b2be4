"""Word-level LM with bucketed sequences + legacy RNN cells
(reference: example/rnn/bucketing/lstm_bucketing.py).

  python examples/train_lm_bucketing.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu import nd, rnn, sym
from mxnet_tpu.module import BucketingModule


def main():
    rs = onp.random.RandomState(0)
    vocab_size, hidden = 50, 32
    sentences = [list(rs.randint(1, vocab_size,
                                 rs.randint(3, 12)).astype(int))
                 for _ in range(256)]
    buckets = [4, 8, 12]
    it = rnn.BucketSentenceIter(sentences, batch_size=16,
                                buckets=buckets, invalid_label=0)

    cell = rnn.LSTMCell(hidden, prefix="lstm_")

    batch_size = 16

    def sym_gen(seq_len):
        data = sym.Variable("data")
        label = sym.Variable("softmax_label")
        embed = sym.embedding(data, sym.Variable("embed_weight"),
                              input_dim=vocab_size, output_dim=hidden,
                              name="embed")
        # static zero initial states keep shape inference closed
        begin = [sym.zeros((batch_size, hidden)),
                 sym.zeros((batch_size, hidden))]
        outputs, _ = cell.unroll(seq_len, embed, begin_state=begin,
                                 merge_outputs=True)
        pred = sym.reshape(outputs, shape=(-1, hidden))
        pred = sym.FullyConnected(pred, num_hidden=vocab_size,
                                  weight=sym.Variable("cls_weight"),
                                  bias=sym.Variable("cls_bias"),
                                  name="cls")
        label = sym.reshape(label, shape=(-1,))
        out = sym.SoftmaxOutput(pred, label, name="softmax")
        return out, ("data",), ("softmax_label",)

    mod = BucketingModule(sym_gen,
                          default_bucket_key=it.default_bucket_key)
    mod.fit(it, optimizer="adam",
            optimizer_params={"learning_rate": 0.01}, num_epoch=3,
            eval_metric="loss")
    print("done; perplexity tracked via eval_metric")


if __name__ == "__main__":
    main()
