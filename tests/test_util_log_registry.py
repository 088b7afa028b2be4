"""mx.util / mx.log / mx.registry / mx.kvstore_server parity
(reference: python/mxnet/{util,log,registry,kvstore_server}.py)."""
import logging
import os
import subprocess
import sys

import numpy as onp
import pytest

import mxnet_tpu as mx


def test_util_np_shape_scope_and_decorator():
    from mxnet_tpu import util

    prev = util.is_np_shape()
    with util.np_shape(True):
        assert util.is_np_shape()
        with util.np_shape(False):
            assert not util.is_np_shape()
        assert util.is_np_shape()
    assert util.is_np_shape() == prev

    @util.np_shape(True)
    def inner():
        return util.is_np_shape()

    assert inner() is True
    assert util.is_np_shape() == prev


def test_util_np_array_and_set_np():
    from mxnet_tpu import util

    util.set_np(shape=True, array=True)
    assert util.is_np_array() and util.is_np_shape()
    util.reset_np()
    assert not util.is_np_array()
    old = util.set_np_shape(True)
    assert util.is_np_shape()
    util.set_np_shape(old)


def test_util_misc_helpers(tmp_path):
    from mxnet_tpu import util

    d = tmp_path / "a" / "b"
    util.makedirs(str(d))
    assert d.is_dir()
    util.makedirs(str(d))  # idempotent

    @util.set_module("mxnet_tpu.fake")
    def f():
        pass

    assert f.__module__ == "mxnet_tpu.fake"

    class NoDoc:
        pass

    del_attr = util.wraps_safely(NoDoc)  # missing __doc__ etc. tolerated

    @del_attr
    def g():
        pass

    assert util.get_gpu_count() >= 0


def test_log_get_logger_format_and_idempotence(tmp_path):
    from mxnet_tpu import log

    f = tmp_path / "x.log"
    lg = log.get_logger("mxtest_file", filename=str(f), level=log.INFO)
    lg2 = log.get_logger("mxtest_file")
    assert lg is lg2 and len(lg.handlers) == 1  # no duplicate handlers
    lg.info("hello %s", "world")
    for h in lg.handlers:
        h.flush()
    text = f.read_text()
    assert "hello world" in text and text[0] == "I"  # level letter prefix
    assert log.getLogger("mxtest_file") is lg


def test_registry_register_alias_create():
    from mxnet_tpu import registry

    class Base:
        def __init__(self, x=1):
            self.x = x

    register = registry.get_register_func(Base, "thing")
    alias = registry.get_alias_func(Base, "thing")
    create = registry.get_create_func(Base, "thing")

    @register
    class Foo(Base):
        pass

    @alias("bar", "baz")
    class Bar(Base):
        pass

    assert isinstance(create("foo"), Foo)
    assert isinstance(create("bar", x=3), Bar)
    assert create("baz").x == 1
    assert set(registry.get_registry(Base)) >= {"foo", "bar", "baz"}
    # instance passthrough
    inst = Foo(7)
    assert create(inst) is inst
    # json config forms
    assert create('["foo", {"x": 9}]').x == 9
    assert isinstance(create('{"thing": "bar"}'), Bar)
    with pytest.raises(AssertionError):
        create("unregistered_name")
    # duplicate registration warns
    with pytest.warns(UserWarning):
        register(Bar, "foo")


def test_kvstore_server_role_exits():
    # reference _init_kvstore_server_module: non-worker roles never run
    # the user script
    code = ("import mxnet_tpu\n"
            "print('SHOULD_NOT_REACH')\n")
    env = dict(os.environ, DMLC_ROLE="server", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=240)
    assert out.returncode == 0
    assert "SHOULD_NOT_REACH" not in out.stdout
    assert "no" in out.stderr.lower() or "exiting" in out.stderr.lower()


def test_kvstore_server_shim_api():
    from mxnet_tpu.kvstore_server import KVStoreServer

    srv = KVStoreServer(kvstore=None)
    srv.run()  # no-op, must not raise
    srv._controller()(0, b"", None)


def test_device_memory_info_surfaces():
    # reference: mx.context.gpu_memory_info / Storage device accounting.
    # On the CPU test backend PJRT may expose no stats — the lenient
    # Storage probe still returns well-formed values, while the strict
    # context API raises on a nonexistent accelerator id (like the
    # reference's cudaMemGetInfo path).
    import jax

    from mxnet_tpu.storage import device_memory_info

    free, total, stats = device_memory_info()
    assert isinstance(stats, dict)
    assert isinstance(free, int) and isinstance(total, int)
    assert free >= 0 and total >= 0
    n_acc = len([d for d in jax.devices() if d.platform != "cpu"])
    if n_acc:
        f2, t2 = mx.context.gpu_memory_info(0)
        assert 0 <= f2 <= max(t2, 1)
    else:
        with pytest.raises(ValueError):
            mx.context.gpu_memory_info(0)


def test_gluon_shape_is_known():
    # reference: gluon/utils.py shape_is_known under both semantics
    from mxnet_tpu.gluon.utils import shape_is_known
    from mxnet_tpu.util import np_shape

    assert shape_is_known((2, 3))
    assert not shape_is_known((2, 0))
    assert not shape_is_known(None)
    assert not shape_is_known(())
    with np_shape(True):
        assert shape_is_known(())
        assert shape_is_known((2, 0))  # zero-size is legal np shape
        assert not shape_is_known((2, -1))
    # invalid negative dims raise like the reference, never "known"
    with pytest.raises(AssertionError):
        shape_is_known((2, -1))  # classic semantics: -1 is invalid
