"""Session factory for the replica children ``tests/test_fleet.py``
spawns: ``spawn_replica`` takes a ``"module:function"`` string, so the
factory has to live in a module a fresh child process can import."""

DENSE = "_fleet_replica:make_dense_session"


def make_dense_session():
    """A three-layer MLP session over (4, 16) inputs, not yet warm:
    the child's ``warmup()`` is what the test reads."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, serving
    from mxnet_tpu.gluon import nn

    mx.random.seed(3)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"),
            nn.Dense(16, activation="relu"),
            nn.Dense(8))
    net.initialize()
    with autograd.pause(train_mode=False):
        net(mx.nd.zeros((1, 16)))
    return serving.InferenceSession(net, input_shapes=[(1, 16)],
                                    buckets=[1, 4], warm=False)
