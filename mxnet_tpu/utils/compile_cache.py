"""Persistent compile-artifact cache + shape-bucketing retrace elimination.

The in-memory executable caches (the eager-dispatch cache in
``ndarray/registry.py`` and the fused train-step cache in
``gluon/fused_step.py``) made the hot path fast *once compiled*, but both
die with the process: every restart re-pays full trace + XLA-compile
cost, and shape variation (bucketed RNN/NLP batches, the last partial
batch, ResizeIter) triggers a retrace storm. This module is the layer
that spans both caches and kills those two costs (the compile-cost
amortization lever TVM, arxiv 1802.04799, and the XLA fusion study,
arxiv 2301.13062, identify as decisive once kernel quality is fixed):

**Disk second tier.** ``fingerprint()`` derives a stable key from the
in-memory cache key — (op/graph fingerprint, avals, donation mask, AMP
version) — salted with the jax/jaxlib/backend/framework versions and a
format version. ``disk_store()`` serializes an AOT-compiled executable
(``jax.jit(...).lower(...).compile()`` →
``jax.experimental.serialize_executable``) under that fingerprint;
``disk_load()`` deserializes it in a later process, so a warm start
reaches steady state without recompiling. Corrupt or version-mismatched
entries are treated as misses (and removed). Entries whose output pytree
contains live functions (the ``jax.vjp`` pullback of recording-mode
dispatch entries) cannot serialize — those count as ``serialize_skips``
and fall back to jax's own persistent compilation cache, which
``_ensure_jax_fallback_cache`` switches on (XLA-compile cost skipped;
tracing still paid).

**Retrace accounting.** ``counting_jit()`` is the blessed ``jax.jit``
wrapper (the ``graft_lint`` ``jit-nocache`` rule flags raw call sites):
it drops a host-side counter tick into the traced body, so *actual*
traces — not calls — are counted, framework-wide. Shape-bucketing wins
and warm-start wins both show up as a flat ``retraces`` counter, and
each trace leaves a ``retrace`` instant with the program's label.

**Compile spans.** ``install_compile_listener()`` listens to the
durations JAX publishes through ``jax.monitoring`` (trace to jaxpr,
lowering, backend compile or cache load, each with ``fun_name``) and
records them as ``compile.trace`` / ``compile.lower`` /
``compile.backend`` spans and as the ``trace_s`` / ``lower_s`` /
``backend_compile_s`` / ``programs`` counters.

**Shape bucketing.** ``plan_bucketing()`` rounds the batch axis of
eligible op dispatches up to a bucket boundary (``MXNET_SHAPE_BUCKETS``:
``pow2`` rounding, or ``mult:N``), so a variable-length stream reuses a
few bucket executables instead of retracing per batch size. Only ops in
the ``_BATCH_SAFE`` table are bucketed — ops whose output rows depend
only on the matching input rows, so padding rows with zeros and slicing
the output back is bitwise row-identical — and only outside autograd
recording. The dispatch cache pads inputs before key lookup and slices
outputs after execution (``pad_batch``/``slice_batch``).

Knobs (``env.py``): ``MXNET_COMPILE_CACHE=0`` disables the disk tier,
``MXNET_COMPILE_CACHE_DIR`` points it somewhere other than
``<checkout>/.jax_cache/mxc``, ``JAX_COMPILATION_CACHE_DIR`` places jax's
own cache (default ``<checkout>/.jax_cache``), ``MXNET_SHAPE_BUCKETS``
enables bucketing. Counters surface via ``profiler.compile_cache_counters()``
and the ``COMPILE_CACHE`` runtime feature.
"""
from __future__ import annotations

import functools
import hashlib
import os
import pickle
import threading
import time
import warnings

import numpy as onp

from ..telemetry import metrics as _telemetry
from ..telemetry import tracer as _telem

__all__ = ["cache_enabled", "cache_dir", "jax_cache_dir", "fingerprint",
           "disk_load", "disk_store", "counting_jit", "note_retrace",
           "install_compile_listener", "aot_compile",
           "load_or_compile", "GuardedCompiled", "bucket_spec",
           "bucket_size", "plan_bucketing", "pad_batch", "slice_batch",
           "compile_cache_stats", "reset_compile_cache_counters"]

FORMAT_VERSION = 1


def _zero_stats():
    return {"disk_hits": 0, "disk_misses": 0, "disk_writes": 0,
            "disk_corrupt": 0, "disk_evicted": 0, "prunes": 0,
            "serialize_skips": 0, "retraces": 0,
            "bucketed_calls": 0, "padded_rows": 0, "true_rows": 0,
            # from jax.monitoring, while MXNET_TELEMETRY >= 1
            "trace_s": 0.0, "lower_s": 0.0, "backend_compile_s": 0.0,
            "programs": 0}


# registry-owned since round 18; the registered "compile_cache" probe
# (compile_cache_stats, + derived pad_ratio) shadows it on read surfaces
_STATS = _telemetry.counter_family("compile_cache", _zero_stats())


def _bump(name, n=1):
    _STATS.add(name, n)


def compile_cache_stats():
    """Disk-tier + retrace + bucketing counters (profiler surface).

    ``pad_ratio`` is total padded rows / total true rows over all
    bucketed dispatches (0.0 when nothing was bucketed)."""
    st = _STATS.snapshot()
    st["pad_ratio"] = (st["padded_rows"] / st["true_rows"]
                       if st["true_rows"] else 0.0)
    st["enabled"] = cache_enabled()
    return st


def reset_compile_cache_counters():
    """Zero the counters (tests, benchmarks). Does not touch the disk
    cache contents — remove the directory for that."""
    _STATS.reset()


# ---------------------------------------------------------------------------
# knobs

def cache_enabled():
    """MXNET_COMPILE_CACHE knob (default on); 0 disables the disk tier
    (the in-memory LRUs are unaffected). Read per use so tests can
    toggle without reimport."""
    from .. import env as _env

    return _env.get_bool("MXNET_COMPILE_CACHE", True)


# the one place a default cache lives: a fixed path inside the checkout
# (git-ignored). The path is part of jax's cache key, so it must not
# move between runs — never a temp name, a pid or a timestamp.
_DEFAULT_CACHE_ROOT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def cache_dir():
    """Directory of the ``.mxc`` tier: MXNET_COMPILE_CACHE_DIR, else the
    fixed in-checkout default ``<checkout>/.jax_cache/mxc``."""
    from .. import env as _env

    return (_env.get_str("MXNET_COMPILE_CACHE_DIR")
            or os.path.join(_DEFAULT_CACHE_ROOT, "mxc"))


def jax_cache_dir():
    """Directory of jax's own persistent compilation cache: where
    ``JAX_COMPILATION_CACHE_DIR`` says, else ``<checkout>/.jax_cache``."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _DEFAULT_CACHE_ROOT


@functools.lru_cache(maxsize=None)  # once per process
def _ensure_jax_fallback_cache():
    """Switch on jax's own persistent compilation cache. It keys
    on the lowered HLO, so it only kicks in after tracing — but that
    still covers the entries the ``.mxc`` tier cannot serialize
    (recording-mode vjp pairs, executor jits): their XLA compile cost
    is skipped on a warm start even though the trace cost is paid again.

    Placement is the environment's: with ``JAX_COMPILATION_CACHE_DIR``
    set jax already reads it and this code sets no directory at all;
    unset, the cache goes to the fixed in-checkout default."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", _DEFAULT_CACHE_ROOT)
    # only compiles worth the disk round-trip: caching every eager
    # micro-prim (min_compile_time 0) measurably TAXES the hot path
    # with serialize+write per prim — the .mxc tier already covers
    # whole dispatch executables, this tier is for the big traced
    # programs (CachedOp, executor, recording-entry first hits)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.05)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


# ---------------------------------------------------------------------------
# fingerprinting

class _Unstable(Exception):
    """A key component has no process-stable canonical form."""


def _canon(v):
    """Process-stable canonical form of a cache-key component.

    Only types whose repr/identity is reproducible across processes are
    admitted — anything else (live functions, closures, arbitrary
    objects whose repr embeds an address) raises ``_Unstable`` and the
    key is simply not persisted. Collision-safety beats coverage here:
    an over-eager canonicalization that maps two different computations
    to one fingerprint would serve the wrong executable."""
    if v is None or isinstance(v, (bool, int, str, bytes)):
        return v
    if isinstance(v, float):
        return ("f", v.hex())
    if isinstance(v, complex):
        return ("c", v.real.hex(), v.imag.hex())
    if isinstance(v, type):
        return ("cls", v.__module__, v.__qualname__)
    if isinstance(v, onp.dtype):
        return ("dt", str(v))
    if isinstance(v, (onp.bool_, onp.integer, onp.floating)):
        return ("np", str(v.dtype), v.item())
    if isinstance(v, slice):
        return ("sl", _canon(v.start), _canon(v.stop), _canon(v.step))
    if isinstance(v, (tuple, list)):
        return (type(v).__name__,) + tuple(_canon(x) for x in v)
    if isinstance(v, dict):
        return ("d",) + tuple(sorted((str(k), _canon(x))
                                     for k, x in v.items()))
    if isinstance(v, frozenset):
        return ("fs",) + tuple(sorted(repr(_canon(x)) for x in v))
    # jnp dtype objects used in avals are numpy dtypes; bfloat16 is an
    # extension type with a stable name
    name = getattr(v, "name", None)
    if name is not None and type(v).__name__ in ("dtype", "type"):
        return ("dt", str(name))
    raise _Unstable(type(v).__name__)


def _salt():
    import jax
    import jaxlib

    from .. import __version__ as fw_version

    return (FORMAT_VERSION, jax.__version__, jaxlib.__version__,
            jax.default_backend(), fw_version)


def fingerprint(kind, key, code_of=()):
    """Stable hex fingerprint of an in-memory cache key, or None when a
    component has no process-stable form (that entry just stays
    memory-only). ``kind`` namespaces the producing cache ('dispatch',
    'fused_step', ...). ``code_of`` lists the functions whose BODIES the
    cached executable was traced from (op body, optimizer kernel, the
    executable builder): their bytecode digests salt the fingerprint, so
    editing an implementation without bumping any version invalidates
    its disk entries instead of silently serving the old computation —
    the cache key alone carries only the op NAME."""
    try:
        canon = (_salt(), str(kind), _canon(key),
                 tuple(code_digest(f) for f in code_of))
    except _Unstable:
        return None
    return hashlib.sha256(repr(canon).encode()).hexdigest()


_CODE_DIGESTS = {}  # weak-keyed via functions' __code__ identity


def code_digest(fn):
    """Digest of a function's bytecode, recursing into nested code
    objects (closures built inside it) — process-stable for identical
    source, different for any edited body. Defaults and closure cells
    are NOT covered (they are runtime values; key material like static
    hyperparameters must ride the cache key itself)."""
    code = getattr(fn, "__code__", None)
    if code is None:
        return ("nocode", getattr(fn, "__module__", ""),
                getattr(fn, "__qualname__", repr(type(fn))))
    cached = _CODE_DIGESTS.get(code)
    if cached is not None:
        return cached
    h = hashlib.sha256()

    def feed(c):
        h.update(c.co_code)
        h.update(repr((c.co_names, c.co_varnames,
                       c.co_consts and tuple(
                           x for x in c.co_consts
                           if isinstance(x, (type(None), bool, int, float,
                                             complex, str, bytes, tuple))
                       ))).encode())
        for const in c.co_consts:
            if isinstance(const, type(c)):
                feed(const)

    feed(code)
    digest = ("code", h.hexdigest())
    _CODE_DIGESTS[code] = digest
    return digest


# ---------------------------------------------------------------------------
# disk tier

def _entry_path(fp):
    return os.path.join(cache_dir(), fp + ".mxc")


def disk_load(fp):
    """Load a serialized executable: (compiled, meta) or None. Any
    failure — missing file, truncated pickle, version drift, pjrt
    deserialize error — is a miss; corrupt files are removed best
    effort so they don't fail every future start."""
    if fp is None or not cache_enabled():
        return None
    with _telem.span("compile_cache.disk_load", cat="io",
                     fp=fp[:16]) as sp:
        out = _disk_load_inner(fp)
        sp.set(hit=out is not None)
        return out


def _disk_load_inner(fp):
    _ensure_jax_fallback_cache()
    path = _entry_path(fp)
    if not os.path.exists(path):
        _bump("disk_misses")
        return None
    # registered fault point (resilience/faults.py): a transient IO
    # failure degrades to a MISS — outside the corruption handler
    # below, which deletes the file: an injected transient must not
    # destroy a valid cache entry (chaos drills would erode the warm
    # start they are testing)
    from ..resilience import faults as _faults

    try:
        _faults.maybe_fail("compile_cache_io")
    except Exception:
        _bump("disk_misses")
        return None
    try:
        with open(path, "rb") as f:
            env = pickle.load(f)
        if env.get("format") != FORMAT_VERSION or env.get("salt") != _salt():
            raise ValueError("compile-cache version mismatch")
        from jax.experimental import serialize_executable as _se

        compiled = _se.deserialize_and_load(env["payload"], env["in_tree"],
                                            env["out_tree"])
    except Exception:
        _bump("disk_corrupt")
        try:
            os.remove(path)
        except OSError:
            pass
        return None
    try:
        os.utime(path)  # mark recency: pruning evicts oldest-used first
    except OSError:
        pass
    _bump("disk_hits")
    return compiled, env.get("meta", {})


def disk_store(fp, compiled, meta=None, key_repr=None):
    """Serialize an AOT-compiled executable under ``fp``; True on a
    completed write. Unserializable executables (live functions in the
    output pytree — e.g. vjp pullbacks) count as ``serialize_skips``;
    IO problems are silent best-effort (a cache must never break the
    step loop)."""
    if fp is None or not cache_enabled():
        return False
    with _telem.span("compile_cache.disk_store", cat="io",
                     fp=fp[:16]) as sp:
        ok = _disk_store_inner(fp, compiled, meta, key_repr)
        sp.set(written=ok)
        return ok


def _disk_store_inner(fp, compiled, meta, key_repr):
    _ensure_jax_fallback_cache()
    try:
        from jax.experimental import serialize_executable as _se

        payload, in_tree, out_tree = _se.serialize(compiled)
        blob = pickle.dumps({"format": FORMAT_VERSION, "salt": _salt(),
                             "meta": dict(meta or {}),
                             "key_repr": key_repr, "payload": payload,
                             "in_tree": in_tree, "out_tree": out_tree})
    except Exception:
        _bump("serialize_skips")
        return False
    try:
        from ..resilience import faults as _faults

        _faults.maybe_fail("compile_cache_io")
        directory = cache_dir()
        os.makedirs(directory, exist_ok=True)
        path = _entry_path(fp)
        tmp = f"{path}.{os.getpid()}.{threading.get_ident()}.tmp"
        with open(tmp, "wb") as f:
            f.write(blob)
        os.replace(tmp, path)  # atomic: concurrent writers race safely
    except Exception:
        # broad on purpose: ANY cache-write failure (disk full, perm,
        # an injected fault) is a skipped write, never a broken step
        return False
    _bump("disk_writes")
    _maybe_prune(directory)
    return True


_PRUNE_EVERY = 32
_prune_tick = [0]


def _maybe_prune(directory):
    """Bound the on-disk tier (the in-memory tiers are LRUs; without
    this the directory grows one serialized executable per fingerprint
    forever — including never-probed stale-salt entries after version
    bumps). Every ``_PRUNE_EVERY``-th write, if the ``.mxc`` total
    exceeds MXNET_COMPILE_CACHE_MAX_MB, the oldest-used entries (mtime:
    refreshed on every load) are removed down to 80% of the cap."""
    _prune_tick[0] += 1
    if _PRUNE_EVERY > 1 and _prune_tick[0] % _PRUNE_EVERY != 1:
        return
    from .. import env as _env

    cap_mb = _env.get_int("MXNET_COMPILE_CACHE_MAX_MB", 1024)
    if cap_mb <= 0:
        return  # 0 = unbounded, explicitly
    entries = []
    try:
        with os.scandir(directory) as it:
            for e in it:
                if not e.name.endswith(".mxc"):
                    continue
                try:
                    st = e.stat()
                except OSError:
                    continue  # pruned/replaced by a concurrent process
                entries.append((st.st_mtime, st.st_size, e.path))
    except OSError:
        return  # directory unreadable/gone: nothing to bound
    total = sum(sz for _, sz, _ in entries)
    cap = cap_mb * 1024 * 1024
    if total <= cap:
        return
    _bump("prunes")
    entries.sort()  # oldest-used first
    for _, sz, path in entries:
        try:
            os.remove(path)
        except OSError:
            continue  # a concurrent pruner won the race for this one
        _bump("disk_evicted")
        total -= sz
        if total <= cap * 0.8:
            break


# ---------------------------------------------------------------------------
# retrace-counted jit + AOT helpers

def note_retrace(label=None):
    """Count one actual trace (called from inside traced bodies, so it
    fires at trace time only — cached executions never reach it) and
    leave a ``retrace`` instant naming the program that was traced."""
    _bump("retraces")
    _telem.instant("retrace", cat="compile", label=label)


# what JAX publishes about a compile -> (span, counter of its seconds)
_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": ("compile.trace", "trace_s"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        ("compile.lower", "lower_s"),
    "/jax/core/compile/backend_compile_duration":
        ("compile.backend", "backend_compile_s"),
}
# published, on a hit only, just before the backend duration of the same
# program on the same thread
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
# A shorter trace is counted but leaves no span. JAX publishes one for
# every jitted jnp function an eager op or a trace goes through: 7,353 in
# one run of the benchmark's OPT cell, of which the 77 of a millisecond
# or more covered 97.5% of the traced time (my chip run, PR 28).
_MIN_TRACE_SPAN_S = 1e-3


class _CompileThread(threading.local):
    """Per-thread state of the listener."""

    def __init__(self):
        self.cache_hit = False  # the persistent cache served the
        # program whose backend duration comes next
        self.traces = []        # (start, end) of counted traces, disjoint,
        # in time order


_COMPILING = _CompileThread()


def _on_jax_duration(event, duration, **attrs):
    """``jax.monitoring`` duration listener: one span per published
    compile phase, ending now and lasting what JAX reports. A trace's
    duration holds those of the jitted functions traced inside it, each
    published before it: the span keeps the whole, the counter adds only
    what no earlier trace of this thread covered."""
    if not _telem.tracing():
        return
    st = _COMPILING
    if event == _CACHE_RETRIEVAL:
        st.cache_hit = True
        return
    spec = _COMPILE_EVENTS.get(event)
    if spec is None:
        return
    name, counter = spec
    t1 = time.monotonic()
    t0 = t1 - duration
    own = duration
    args = {"fun_name": attrs.get("fun_name")}
    if name == "compile.trace":
        inner = st.traces
        while inner and inner[-1][1] > t0:
            a, b = inner.pop()
            own -= b - a
        inner.append((t0, t1))
        del inner[:-1024]
    elif name == "compile.backend":
        args["cache_hit"] = st.cache_hit
        st.cache_hit = False
        _bump("programs")
    _bump(counter, max(0.0, own))
    if name == "compile.trace" and duration < _MIN_TRACE_SPAN_S:
        return
    _telem.emit_span(name, "compile", t0, t1,
                     parent=_telem.current_span_id(), **args)


@functools.lru_cache(maxsize=None)  # once per process
def install_compile_listener():
    """Register :func:`_on_jax_duration` with ``jax.monitoring`` (the
    package does at import). With ``MXNET_TELEMETRY=0`` the listener
    returns after its level check."""
    from jax import monitoring

    monitoring.register_event_duration_secs_listener(_on_jax_duration)


def counting_jit(fun, label=None, **jit_kwargs):
    """``jax.jit`` with retrace accounting — the blessed way to jit
    inside ``mxnet_tpu`` (the ``graft_lint`` ``jit-nocache`` rule flags
    raw ``jax.jit`` call sites). The wrapper ticks the ``retraces``
    counter from inside the traced body: jit-cache hits never re-enter
    the Python body, so the counter measures traces, not calls."""
    import jax

    if cache_enabled():
        # even entries this tier can't serialize (vjp pairs, executor
        # closures) get their XLA-compile cost cached across processes
        _ensure_jax_fallback_cache()
    name = label or getattr(fun, "__name__", "fn")

    @functools.wraps(fun)
    def counted(*args, **kwargs):
        note_retrace(name)
        return fun(*args, **kwargs)

    # jax names the program after the function: jit_<label> on a trace's
    # "XLA Modules" line and fun_name in the compile spans
    counted.__name__ = counted.__qualname__ = name

    return jax.jit(counted, **jit_kwargs)  # graft-lint: allow(jit-nocache)


def aot_compile(jitted, *args, **kwargs):
    """``jitted.lower(*args).compile()`` with backend donation warnings
    suppressed (CPU warns that donation is unimplemented at lowering
    time; the hint is best-effort by design). Returns the ``Compiled``
    handle — the serializable artifact the disk tier stores."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        return jitted.lower(*args, **kwargs).compile()


def load_or_compile(fp, jitted, args, meta=None):
    """The whole warm-start story as one call: deserialize the disk
    entry under ``fp`` if present, else AOT-compile ``jitted`` over
    ``args`` (avals or concrete arrays) and persist it. Returns
    ``(fn, meta, from_disk)`` where ``fn`` is a :class:`GuardedCompiled`
    (any aval mismatch or stale artifact degrades to the jit path
    rather than erroring the caller). ``meta`` may be a dict or a
    zero-arg callable evaluated AFTER the fresh compile — so metadata
    derived at trace time (output arity, tree structure) can ride the
    envelope for warm processes that never trace. ``fp=None`` (an
    unstable key) compiles memory-only."""
    loaded = disk_load(fp)
    if loaded is not None:
        compiled, m = loaded
        return GuardedCompiled(compiled, jitted), m, True
    compiled = aot_compile(jitted, *args)
    m = dict(meta() if callable(meta) else (meta or {}))
    disk_store(fp, compiled, meta=m)
    return GuardedCompiled(compiled, jitted), m, False


class GuardedCompiled:
    """Callable facade over an AOT/deserialized ``Compiled`` with a
    jitted fallback: ``Compiled`` objects are specialized to exact
    input avals (including weak_type and sharding), so any mismatch —
    or a stale on-disk artifact — degrades permanently to the plain
    ``jax.jit`` path instead of erroring the caller's step loop."""

    __slots__ = ("_compiled", "_jfn")

    def __init__(self, compiled, jfn):
        self._compiled = compiled
        self._jfn = jfn

    def __call__(self, *args):
        compiled = self._compiled
        if compiled is not None:
            try:
                return compiled(*args)
            except Exception:
                self._compiled = None
        return self._jfn(*args)


# ---------------------------------------------------------------------------
# shape bucketing

_SPEC_CACHE = {}


def bucket_spec():
    """Parsed MXNET_SHAPE_BUCKETS policy: None (off, the default),
    ('pow2',) or ('mult', N). '1' enables the default pow2 policy."""
    from .. import env as _env

    raw = _env.get_str("MXNET_SHAPE_BUCKETS")
    if raw is None:
        return None
    spec = _SPEC_CACHE.get(raw)
    if spec is None:
        spec = _parse_spec(raw)
        _SPEC_CACHE[raw] = spec
    return spec or None


def _parse_spec(raw):
    raw = raw.strip()
    if raw in ("", "0", "false", "False", "off"):
        return ()
    if raw in ("1", "pow2", "true", "True", "on"):
        return ("pow2",)
    if raw.startswith("mult:"):
        try:
            n = int(raw.split(":", 1)[1])
        except ValueError:
            n = 0
        if n > 1:
            return ("mult", n)
    import logging

    logging.warning("invalid MXNET_SHAPE_BUCKETS=%r; bucketing disabled "
                    "(expected 0 | pow2 | mult:N)", raw)
    return ()


def bucket_size(n, spec):
    """Bucket boundary for a batch of ``n`` rows under ``spec``."""
    if n <= 1:
        return n
    if spec[0] == "pow2":
        b = 1
        while b < n:
            b <<= 1
        return b
    return -(-n // spec[1]) * spec[1]  # mult:N — round up to multiple


# op -> bucketing rule. "ew": elementwise/broadcast — every max-rank
# operand whose axis 0 equals the batch is padded, lower-rank /
# broadcast (axis0 == 1) operands pass through, and output rows are
# independent per input row. ("row", (slots...), guard): only the given
# operand slots carry the batch on axis 0 (rank >= 2 required — on a
# 1-D operand axis 0 is the data/contraction axis, not a batch);
# ``guard(config, datas)`` sees the op's full config — positional
# literals bound through the op signature included — and vetoes
# configs that mix rows (e.g. transposed dot, softmax over axis 0).
# Everything NOT in this table is never bucketed: padding is only
# row-bitwise-identical when no output row reads another input row.

def _softmax_axis_ok(config, datas):
    if config.get("use_length") or config.get("length") is not None:
        return False
    axis = config.get("axis", -1)
    if not isinstance(axis, int):
        return False
    # resolve against rank: axis=-2 on 2-D (or any alias of axis 0)
    # normalizes over the batch axis and padded rows would leak into
    # the denominator
    return axis % datas[0].ndim != 0


def _dot_rowwise(config, datas):
    return not config.get("transpose_a", False)


def _fc_flatten(config, datas):
    return bool(config.get("flatten", True))


_BATCH_SAFE = {
    # elementwise / broadcast arithmetic
    "broadcast_add": "ew", "broadcast_sub": "ew", "broadcast_mul": "ew",
    "broadcast_div": "ew", "broadcast_power": "ew",
    "broadcast_maximum": "ew", "broadcast_minimum": "ew",
    "elemwise_add": "ew", "elemwise_sub": "ew", "elemwise_mul": "ew",
    "elemwise_div": "ew",
    # elementwise math
    "tanh": "ew", "sigmoid": "ew", "relu": "ew", "exp": "ew", "log": "ew",
    "sqrt": "ew", "square": "ew", "abs": "ew", "negative": "ew",
    "clip": "ew",
    # rowwise NN ops: output row i is a function of input row i only
    "activation": ("row", (0,), None),
    "fully_connected": ("row", (0,), _fc_flatten),
    "flatten": ("row", (0,), None),
    "softmax": ("row", (0,), _softmax_axis_ok),
    "log_softmax": ("row", (0,), _softmax_axis_ok),
    "dot": ("row", (0,), _dot_rowwise),
}


def register_batch_safe(opname, rule):
    """Extension point: declare an op safe for batch-axis bucketing.
    ``rule`` is "ew" or ("row", (slots...), guard_or_None) — see the
    ``_BATCH_SAFE`` table comment for the row-independence contract the
    op must honor."""
    _BATCH_SAFE[opname] = rule


def _bound_config(opname, arg_template, kwargs):
    """The op's config as the body sees it: kwargs plus POSITIONAL
    literals bound to their parameter names through the op signature
    (``nd.softmax(x, None, 0)`` passes axis positionally — a guard that
    only saw kwargs would miss the row-mixing axis). None when binding
    fails: an unresolvable config must veto, not pass."""
    merged = dict(kwargs)
    if all(t[0] == "arr" for t in arg_template):
        return merged
    from ..ndarray.registry import get_op

    opdef = get_op(opname)
    if opdef is None:
        return None
    try:
        pos = [_ARR if t[0] == "arr" else t[1] for t in arg_template]
        bound = opdef.signature().bind_partial(*pos)
    except TypeError:
        return None
    for name, val in bound.arguments.items():
        if val is not _ARR and name not in merged:
            merged[name] = val
    return merged


_ARR = object()  # placeholder for array operands during bind_partial


def plan_bucketing(opname, datas, arg_template, kwargs):
    """(padded_batch, true_batch, pad_slots) when this dispatch should
    run through a bucket executable, else None. ``pad_slots`` indexes
    ``datas``. Conservative: any operand layout or config the rule
    cannot prove row-independent vetoes the plan."""
    spec = bucket_spec()
    if spec is None or not datas:
        return None
    rule = _BATCH_SAFE.get(opname)
    if rule is None:
        return None
    if rule == "ew":
        ndim = max(d.ndim for d in datas)
        if ndim == 0:
            return None
        batch = max((d.shape[0] for d in datas if d.ndim == ndim),
                    default=0)
        if batch <= 1:
            return None
        slots = []
        for i, d in enumerate(datas):
            if d.ndim == ndim and d.shape[0] == batch:
                slots.append(i)
            elif d.ndim == ndim and d.shape[0] != 1:
                return None  # ragged axis-0 mix: not a broadcast layout
        if not slots:
            return None
    else:
        _, arg_slots, guard = rule
        slots = [s for s in arg_slots if s < len(datas)]
        if not slots:
            return None
        # rank >= 2: on a 1-D operand axis 0 is the data/contraction
        # axis (dot lhs, softmax vector), never a batch to pad
        if any(datas[s].ndim < 2 for s in slots):
            return None
        if guard is not None:
            config = _bound_config(opname, arg_template, kwargs)
            if config is None:
                return None
            try:
                if not guard(config, datas):
                    return None
            except Exception:
                return None
        batch = datas[slots[0]].shape[0]
        if batch <= 1:
            return None
        if any(datas[s].shape[0] != batch for s in slots):
            return None
    padded = bucket_size(batch, spec)
    if padded == batch:
        return None
    return padded, batch, tuple(slots)


def pad_batch(data, padded):
    """Zero-pad axis 0 up to the bucket boundary (zeros: safe for every
    whitelisted op — padded rows may compute inf/nan garbage, but those
    rows are sliced off before anyone reads them)."""
    import jax.numpy as jnp

    n = data.shape[0]
    if n == padded:
        return data
    return jnp.concatenate(
        [data, jnp.zeros((padded - n,) + data.shape[1:], data.dtype)], 0)


def slice_batch(data, padded, true):
    """Undo ``pad_batch`` on an output whose axis 0 is the padded
    batch."""
    if data.ndim and data.shape[0] == padded:
        return data[:true]
    return data


def note_bucketed(padded, true):
    _bump("bucketed_calls")
    _bump("padded_rows", padded - true)
    _bump("true_rows", true)
