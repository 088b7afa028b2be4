"""Global RNG state + key provider.

TPU-native redesign of the reference RNG (reference:
include/mxnet/random_generator.h per-thread Philox states;
src/resource.cc:174-198 global/per-ctx seeding; python/mxnet/random.py).
JAX's counter-based PRNG replaces mutable generator state: a module-level
key is split per draw in eager mode, and a *key provider* stack lets traced
regions (CachedOp / hybridized blocks) thread an explicit key argument so
sampling stays pure under jit — the idiomatic TPU answer to MXNet's
stateful kParallelRandom resource.
"""
from __future__ import annotations

import threading

import jax

__all__ = ["seed", "next_key", "key_provider", "uniform", "normal", "randn",
           "randint", "exponential", "poisson", "gamma", "negative_binomial",
           "generalized_negative_binomial", "multinomial"]


# process-wide base seed: fresh per-thread states derive from it (with a
# thread-id fold-in so threads draw DIFFERENT streams), and mx.random.seed
# re-seeds it for threads created afterwards
_GLOBAL_SEED = [0]


class _RngState(threading.local):
    def __init__(self):
        # the base key is derived on first use, not here: importing the
        # package must not initialise a jax backend (a fleet router
        # parent that did would hold the chip against its replicas)
        self._key = None
        self.providers = []

    @property
    def key(self):
        if self._key is None:
            base = jax.random.PRNGKey(_GLOBAL_SEED[0])
            if threading.current_thread() is not threading.main_thread():
                base = jax.random.fold_in(base, threading.get_ident()
                                          & 0x7FFFFFFF)
            self._key = base
        return self._key

    @key.setter
    def key(self, value):
        self._key = value


_STATE = _RngState()


def seed(seed_state, ctx="all"):
    """Set the global seed (reference: mx.random.seed,
    python/mxnet/random.py; MXRandomSeed → ResourceManager SeedRandom
    src/resource.cc:174). Applies to this thread immediately and to
    threads created afterwards via the process-wide base seed."""
    _GLOBAL_SEED[0] = int(seed_state)
    _STATE.key = jax.random.PRNGKey(int(seed_state))
    _STATE.providers = []


def next_key():
    """Next PRNG key: from the innermost provider (traced region) or by
    splitting the global eager key."""
    if _STATE.providers:
        return _STATE.providers[-1]()
    _STATE.key, sub = jax.random.split(_STATE.key)
    return sub


class key_logger:
    """Record the keys an op draws while tracing, delegating to whatever
    source is active (the global stream, or an enclosing provider such as
    CachedOp's key argument). The eager tape stores the logged keys so
    higher-order replay (autograd create_graph) re-derives gradients
    against the SAME random masks the forward used."""

    def __init__(self):
        self.keys = []
        self._installed = False

    def __enter__(self):
        if _STATE.providers:
            # an enclosing provider (CachedOp trace) owns key derivation;
            # its keys may be tracers — do not capture them on the eager
            # tape (CachedOp pins its own keys via tape_fun)
            return self

        def provider():
            _STATE.key, sub = jax.random.split(_STATE.key)
            self.keys.append(sub)
            return sub

        _STATE.providers.append(provider)
        self._installed = True
        return self

    def __exit__(self, *exc):
        if self._installed:
            _STATE.providers.pop()


class key_replayer:
    """Feed back keys captured by a key_logger, in order. Extra draws
    beyond the log fall through to the global stream (defensive — a
    primal fn draws a fixed number of keys per trace). With
    ``strict=True`` an extra draw raises instead: the compiled-dispatch
    cache pre-splits exactly the counted number of keys and passes them
    as executable arguments, so a fall-through split under jit would
    bake a concrete key into the compiled executable as a constant —
    silently reusing one mask forever. Raising turns that into a trace
    failure the dispatch layer catches and falls back from."""

    def __init__(self, keys, strict=False):
        self._keys = list(keys)
        self._i = 0
        self._strict = strict

    def _next(self):
        if self._i < len(self._keys):
            k = self._keys[self._i]
            self._i += 1
            return k
        if self._strict:
            raise RuntimeError(
                "op drew more PRNG keys than were pre-split for replay")
        _STATE.key, sub = jax.random.split(_STATE.key)
        return sub

    def __enter__(self):
        _STATE.providers.append(self._next)
        return self

    def __exit__(self, *exc):
        _STATE.providers.pop()


class key_provider:
    """Context manager installing a key source for traced regions.

    CachedOp tracing installs a provider that derives keys from an explicit
    key *argument* of the jitted function, so randomness is an input, not a
    baked-in constant.
    """

    def __init__(self, base_key):
        self._base = base_key
        self._count = 0

    def _next(self):
        k = jax.random.fold_in(self._base, self._count)
        self._count += 1
        return k

    def __enter__(self):
        _STATE.providers.append(self._next)
        return self

    def __exit__(self, *exc):
        _STATE.providers.pop()

    @property
    def used(self):
        return self._count > 0


# eager sampling API (mx.random.*) — thin over the registered ops
def _nd():
    from . import ndarray as nd

    return nd


def uniform(low=0, high=1, shape=(1,), dtype="float32", ctx=None, out=None):
    return _nd().random_uniform(low=low, high=high, shape=shape, dtype=dtype,
                                out=out)


def normal(loc=0, scale=1, shape=(1,), dtype="float32", ctx=None, out=None):
    return _nd().random_normal(loc=loc, scale=scale, shape=shape, dtype=dtype,
                               out=out)


def randn(*shape, loc=0.0, scale=1.0, dtype="float32", ctx=None):
    return normal(loc, scale, shape or (1,), dtype, ctx)


def randint(low, high, shape=(1,), dtype="int32", ctx=None, out=None):
    return _nd().random_randint(low=low, high=high, shape=shape, dtype=dtype,
                                out=out)


def exponential(scale=1.0, shape=(1,), dtype="float32", ctx=None, out=None):
    return _nd().random_exponential(lam=1.0 / scale, shape=shape, dtype=dtype,
                                    out=out)


def poisson(lam=1.0, shape=(1,), dtype="float32", ctx=None, out=None):
    return _nd().random_poisson(lam=lam, shape=shape, dtype=dtype, out=out)


def gamma(alpha=1.0, beta=1.0, shape=(1,), dtype="float32", ctx=None, out=None):
    return _nd().random_gamma(alpha=alpha, beta=beta, shape=shape, dtype=dtype,
                              out=out)


def negative_binomial(k=1, p=1, shape=(1,), dtype="float32", ctx=None, out=None):
    return _nd().random_negative_binomial(k=k, p=p, shape=shape, dtype=dtype,
                                          out=out)


def generalized_negative_binomial(mu=1, alpha=1, shape=(1,), dtype="float32",
                                  ctx=None, out=None):
    return _nd().random_generalized_negative_binomial(
        mu=mu, alpha=alpha, shape=shape, dtype=dtype, out=out)


def multinomial(data, shape=(1,), get_prob=False, dtype="int32"):
    return _nd().sample_multinomial(data, shape=shape, get_prob=get_prob,
                                    dtype=dtype)
