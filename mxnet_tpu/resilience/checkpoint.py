"""CheckpointManager: crash-consistent snapshots of COMPLETE training
state.

``model.py``'s ``save_checkpoint`` (the reference parity surface) dumps
parameters only — a restart from it replays the optimizer from scratch,
re-draws different dropout masks, and forgets the AMP loss scale. This
manager snapshots everything a mid-epoch resume needs for BITWISE
parity with an uninterrupted run:

- parameters (creation order, by name),
- optimizer state (``Trainer._states``, the ``save_states`` tree),
- the update counters — ``num_update`` / ``begin_num_update`` /
  per-index counts / the AMP skip-step total (device-resident
  fused-step state is synced into the host mirrors first),
- the AMP :class:`LossScaler` (scale + grow-window position),
- the global PRNG stream position (``mxnet_tpu.random``), so dropout
  masks after a resume match the uninterrupted stream,
- kvstore contents (+ server-side updater state when present),
- the data cursor (epoch/step — whatever dict the caller passes;
  ``DeviceFeed.position`` feeds it).

**Crash consistency.** A checkpoint is a DIRECTORY written under a
temporary name and atomically renamed into place, carrying a
``manifest.json`` with per-file sha256 content hashes salted by the
framework/jax versions. A crash mid-write leaves only a ``.tmp-*``
directory (cleaned on the next save); a torn/corrupted/version-drifted
checkpoint fails hash validation and ``latest_valid`` falls back to
the previous good one with a warning — a restart NEVER loads a
half-written state (the property ps-lite servers get from applying
pushes transactionally; reference kvstore_dist_server.h).

**Async snapshots.** jax arrays are immutable, so *capturing* a
snapshot is just collecting references — plus device-side copies for
the buffers the fused step donates (``fused_step.state_copy``; donation
deletes the original even while Python references it). The
device→host transfer, pickling, hashing and file IO then run on a
background writer thread (``MXNET_CKPT_ASYNC``, default on), so the
step loop pays only the capture. ``wait()`` joins the writer; a
writer failure surfaces on the next ``save``/``wait``.

Retention: ``keep`` newest checkpoints are kept (``MXNET_CKPT_KEEP``,
default 3); older ones are pruned after each successful write.

**Sharded checkpoints (round 15).** When training runs under a
``sharding.plan_scope``, parameter and optimizer-state buffers live
sharded across the mesh. Saving gathers nothing: each non-replicated
device buffer becomes a placeholder in the main payload, and every
device's local shards land in a per-device ``shard-NNN.pkl`` file
(its own ``checkpoint_shard_write`` fault seam, same hash-manifested
atomic-rename discipline). The manifest's ``sharding`` section records
the mesh axes/shape and per-entry partition specs. Restore is
**mesh-shape agnostic**: the saved global index slices reassemble the
full host array regardless of the writer's mesh, so a checkpoint saved
on a 1x4 mesh restores onto 2x2, a single device, or any other shape
(``ckpt_reshards`` counts restores whose active mesh differs from the
writer's); under an active plan scope the restored buffers are placed
straight back at the plan's layouts.

**Serving session state (round 16).** ``session_state=`` attaches a
:class:`~mxnet_tpu.serving.state.SessionStateStore`: each save rides a
host snapshot of every live client's recurrent/KV state rows
(``export_state``), and ``restore`` re-opens those sessions into the
attached store (``restore_state``), so a server restart — or a canary
promote that hands the checkpoint to the successor — resumes mid-stream
decodes instead of dropping them.
"""
from __future__ import annotations

import hashlib
import json
import logging
import os
import pickle
import queue as _queue
import shutil
import threading
import time

from ..base import MXNetError

__all__ = ["CheckpointManager"]

FORMAT_VERSION = 1
_PAYLOAD = "state.pkl"
_MANIFEST = "manifest.json"
#: placeholder marker substituted for mesh-sharded buffers in the main
#: payload; ``load`` swaps the reassembled full array back in
_SHARD_REF = "__mxnet_shard_ref__"


def _log():
    return logging.getLogger(__name__)


def _salt():
    import jax

    from .. import __version__ as fw_version

    return [FORMAT_VERSION, fw_version, jax.__version__,
            jax.default_backend()]


def _hash(content, salt):
    """Version-salted content hash: a checkpoint written by a different
    framework/jax build fails validation instead of restoring state the
    new build would silently misinterpret."""
    h = hashlib.sha256()
    h.update(repr(salt).encode())
    h.update(content)
    return h.hexdigest()


def _is_device_array(x):
    import jax

    return isinstance(x, jax.Array)


_BULK_COPY = [None]


def _bulk_copy(arrays):
    """Device copies of a list of arrays in ONE compiled dispatch.

    Capture must copy every buffer the fused step will donate (holding
    a reference does not survive donation), and per-array ``jnp.array``
    calls cost ~0.2ms of dispatch each — the dominant step-thread cost
    of an async save. One jitted tree-copy pays one dispatch for the
    whole snapshot; jit caches per aval signature, so steady-state
    saves never retrace."""
    if not arrays:
        return []
    if _BULK_COPY[0] is None:
        import jax.numpy as jnp

        from ..utils import compile_cache as cc

        _BULK_COPY[0] = cc.counting_jit(
            lambda xs: tuple(jnp.array(x, copy=True) for x in xs),
            label="ckpt_bulk_copy")
    return list(_BULK_COPY[0](list(arrays)))


def _to_host(tree):
    """Device arrays -> numpy, recursively; everything else verbatim.
    Runs on the WRITER thread in async mode — the step loop never pays
    the D2H sync."""
    import numpy as onp

    if _is_device_array(tree):
        return onp.asarray(tree)
    if isinstance(tree, tuple):
        return tuple(_to_host(v) for v in tree)
    if isinstance(tree, list):
        return [_to_host(v) for v in tree]
    if isinstance(tree, dict):
        return {k: _to_host(v) for k, v in tree.items()}
    return tree


class CheckpointManager:
    """Atomic, validated, keep-last-N checkpoint store (see module
    docstring).

    Parameters
    ----------
    directory : str, optional — checkpoint root (created if absent);
        default ``MXNET_CKPT_DIR`` or ``$MXNET_HOME/checkpoints``
    trainer : gluon.Trainer, optional — snapshots parameters +
        optimizer/scaler/counter state
    params : list of Parameter, optional — explicit parameter set
        (default: the trainer's)
    kvstore : KVStore, optional — snapshots store contents (+ updater
        state)
    keep : int — retention bound (default ``MXNET_CKPT_KEEP``)
    async_mode : bool — background writer thread (default
        ``MXNET_CKPT_ASYNC``)
    include_prng : bool — snapshot/restore the global PRNG stream
        position (default True; bitwise resume needs it whenever the
        forward draws keys — dropout, sampled ops)
    session_state : serving.SessionStateStore, optional — snapshots
        every live serving session's state rows and resumes them on
        restore (stateful continuous-batching serving)
    """

    def __init__(self, directory=None, trainer=None, params=None,
                 kvstore=None, keep=None, async_mode=None,
                 include_prng=True, session_state=None):
        from .. import env as _env

        if directory is None:
            directory = _env.get_str("MXNET_CKPT_DIR")
        if not directory:
            home = _env.get_str(
                "MXNET_HOME",
                os.path.join(os.path.expanduser("~"), ".mxnet"))
            directory = os.path.join(home, "checkpoints")
        self.directory = directory
        self.trainer = trainer
        self._params = params
        self.kvstore = kvstore
        self.keep = int(keep if keep is not None else
                        _env.get_int("MXNET_CKPT_KEEP", 3))
        self.async_mode = bool(
            async_mode if async_mode is not None else
            _env.get_bool("MXNET_CKPT_ASYNC", True))
        self.include_prng = bool(include_prng)
        self.session_state = session_state
        # one persistent writer thread over a BOUNDED job queue: the
        # step loop pays only the capture; serialize + IO overlap the
        # next steps, and a producer outrunning the writer blocks at
        # the bound instead of ballooning snapshots in memory
        self._q = None           # lazy: many managers never go async
        self._writer = None
        self._write_error = None
        os.makedirs(self.directory, exist_ok=True)
        self._clean_stale_tmp()

    # -- layout --------------------------------------------------------

    def _dir_for(self, step):
        return os.path.join(self.directory, f"ckpt-{int(step):012d}")

    def list_steps(self):
        """All checkpoint step numbers on disk (valid or not),
        ascending."""
        steps = []
        try:
            for name in os.listdir(self.directory):
                if name.startswith("ckpt-"):
                    try:
                        steps.append(int(name[5:]))
                    except ValueError:
                        pass
        except OSError:
            pass
        return sorted(steps)

    def _clean_stale_tmp(self):
        """Remove half-written ``.tmp-*`` directories a crashed writer
        left behind (they are invisible to loads either way — cleanup
        just reclaims the disk)."""
        try:
            for name in os.listdir(self.directory):
                if name.startswith(".tmp-"):
                    shutil.rmtree(os.path.join(self.directory, name),
                                  ignore_errors=True)
        except OSError:
            pass

    # -- validation ----------------------------------------------------

    def validate(self, step):
        """True iff the checkpoint at ``step`` is complete and its
        content hashes (version-salted) match the manifest."""
        d = self._dir_for(step)
        try:
            with open(os.path.join(d, _MANIFEST)) as f:
                manifest = json.load(f)
            if manifest.get("format") != FORMAT_VERSION:
                return False
            salt = manifest.get("salt")
            if salt != _salt():
                return False
            for fname, info in manifest.get("files", {}).items():
                with open(os.path.join(d, fname), "rb") as f:
                    content = f.read()
                if len(content) != info.get("bytes") or \
                        _hash(content, salt) != info.get("sha256"):
                    return False
            return True
        except (OSError, ValueError, KeyError):
            return False

    def latest_valid(self):
        """The newest step whose checkpoint validates, or None. Invalid
        (torn/corrupt/version-drifted) checkpoints are skipped with a
        warning — the fallback the atomic-write discipline exists to
        guarantee."""
        from . import _count

        for step in reversed(self.list_steps()):
            if self.validate(step):
                return step
            _count("ckpt_corrupt_skipped")
            _log().warning(
                "checkpoint %s is corrupt or incomplete; falling back "
                "to the previous one", self._dir_for(step))
        return None

    # -- capture -------------------------------------------------------

    def _capture(self, step, cursor, extra):
        """Collect the full state tree NOW (device references + device
        copies of donated buffers + host scalars). Cheap — no D2H
        sync, no file IO — so async saves barely touch the step loop."""
        snap = {"step": int(step), "cursor": dict(cursor or {}),
                "extra": extra,
                "trainer": None, "params": None, "prng": None,
                "kvstore": None, "session_state": None}
        if self.session_state is not None:
            # already pure host primitives — the writer thread pickles
            # it unchanged, and a promote can hand it to the successor
            snap["session_state"] = self.session_state.export_state()
        trainer = self.trainer
        params = self._params
        if params is None and trainer is not None:
            params = trainer._params
        if trainer is not None:
            snap["trainer"] = self._capture_trainer(trainer)
        if params is not None:
            from .. import gluon  # noqa: F401 — Parameter lives there
            from ..gluon import fused_step as _fs

            live = [p for p in params
                    if getattr(p, "_ndarray", None) is not None]
            if _fs.donate_params_enabled():
                # donated buffers do not survive the next step: copy
                # (one bulk dispatch); plain refs suffice otherwise
                # (jax arrays are immutable)
                copies = _bulk_copy([p._ndarray._data for p in live])
                snap["params"] = [(p.name, c)
                                  for p, c in zip(live, copies)]
            else:
                snap["params"] = [(p.name, p._ndarray._data)
                                  for p in live]
        if self.include_prng:
            from .. import random as _mxrandom

            snap["prng"] = {"global_seed": _mxrandom._GLOBAL_SEED[0],
                            "key": _mxrandom._STATE.key}
        if self.kvstore is not None:
            snap["kvstore"] = self._capture_kvstore(self.kvstore)
        return snap

    @staticmethod
    def _capture_trainer(trainer):
        from .. import ndarray as nd
        from ..gluon import fused_step as _fs

        # in-flight async-grad-sync speculation must not leak across a
        # snapshot/restore boundary (the load_states round-trip rule)
        trainer._abandon_speculation()
        # device-resident fused-step state (skip-drifted update count,
        # loss scale) is authoritative — pull it into the host mirrors
        trainer._sync_fused_state()
        if not trainer._states_created:
            trainer._create_states()

        bufs = []

        def cap(v):
            if isinstance(v, nd.NDArray):
                # the fused step DONATES state buffers: a bare device
                # reference dies at the next step even while we hold
                # it — snapshot a device copy (one bulk dispatch for
                # the whole tree, filled in below)
                bufs.append(v.data)
                return ("nd", len(bufs) - 1)
            if isinstance(v, tuple):
                return ("tuple", tuple(cap(s) for s in v))
            return ("raw", v)

        def fill(v, copies):
            tag, val = v
            if tag == "nd":
                return ("nd", copies[val])
            if tag == "tuple":
                return ("tuple", tuple(fill(s, copies) for s in val))
            return v

        skeleton = [cap(s) for s in trainer._states]
        copies = _bulk_copy(bufs)
        optim = trainer._optimizer
        payload = {
            "num_update": optim.num_update,
            "begin_num_update": optim.begin_num_update,
            "index_update_count": dict(optim._index_update_count),
            "fused_skips": trainer._fused_skipped_steps(),
            "states": [fill(s, copies) for s in skeleton],
            "scaler": None,
        }
        scaler = getattr(trainer, "_amp_loss_scaler", None)
        if scaler is not None:
            payload["scaler"] = {
                "loss_scale": scaler._loss_scale,
                "unskipped": scaler._unskipped,
                "scale_factor": scaler._scale_factor,
                "scale_window": scaler._scale_window}
        return payload

    @staticmethod
    def _capture_kvstore(kv):
        from ..ndarray import sparse as _sp

        if getattr(kv, "_async_mode", False):
            kv._async_flush()  # pending pushes must land in the snapshot
        values = {}
        for k, v in kv._store.items():
            if isinstance(v, _sp.BaseSparseNDArray):
                v = v.todense()
            values[k] = v.data
        updater_states = None
        updater = getattr(kv, "_updater", None)
        if updater is not None and hasattr(updater, "get_states"):
            updater_states = updater.get_states(dump_optimizer=False)
        return {"values": values, "updater_states": updater_states}

    # -- write ---------------------------------------------------------

    def save(self, step, cursor=None, extra=None):
        """Snapshot now; write inline (sync mode) or enqueue to the
        writer thread (async mode; at most two snapshots are in
        flight — a producer outrunning the writer blocks at the bound,
        counted as ``ckpt_async_waits``). Raises any pending writer
        failure. Returns the checkpoint directory path the write will
        land at."""
        from . import _count

        self._raise_pending()
        snap = self._capture(step, cursor, extra)
        if self.async_mode:
            q = self._ensure_writer()
            try:
                q.put_nowait(snap)
            except _queue.Full:
                _count("ckpt_async_waits")
                q.put(snap)
            _count("ckpt_async_saves")
        else:
            self._write(snap)
        return self._dir_for(step)

    def wait(self):
        """Block until every enqueued async write completed; re-raise
        the first failure."""
        if self._q is not None:
            self._q.join()
        self._raise_pending()

    def _ensure_writer(self):
        """The lazy persistent writer thread. It must NOT hold a strong
        reference to this manager: a dropped manager (and the trainer +
        parameters it carries) would otherwise be pinned by its own
        writer forever — the kvstore applier's weakref discipline. The
        finalizer posts the None sentinel that releases the thread."""
        if self._q is None:
            import weakref

            q = self._q = _queue.Queue(maxsize=2)
            ref = weakref.ref(self)

            def loop():
                while True:
                    snap = q.get()
                    try:
                        if snap is None:
                            return
                        mgr = ref()
                        if mgr is None:
                            return
                        try:
                            mgr._write(snap)
                        except BaseException as e:  # noqa: BLE001
                            # surfaced on the next save()/wait()
                            if mgr._write_error is None:
                                mgr._write_error = e
                        finally:
                            del mgr
                    finally:
                        q.task_done()

            self._writer = threading.Thread(
                target=loop, name="mxnet-ckpt-writer", daemon=True)
            self._writer.start()
            weakref.finalize(self, q.put, None)
        return self._q

    def _raise_pending(self):
        err, self._write_error = self._write_error, None
        if err is not None:
            raise MXNetError(
                f"background checkpoint write failed: {err}") from err

    @staticmethod
    def _extract_shards(snap):
        """Pull mesh-sharded buffers out of the snapshot tree.

        Returns ``(snap, shard_meta, shard_blobs)``: the tree with each
        non-replicated multi-device array replaced by a
        ``(_SHARD_REF, idx)`` placeholder, the manifest ``sharding``
        section, and ``{device_ordinal: [(idx, slices, np_shard), ...]}``
        — every device's LOCAL shards plus their global index slices,
        so restore reassembles the full array on ANY mesh shape.
        Replicated and single-device buffers stay in the main payload
        (no point writing N identical copies). ``(snap, None, {})``
        when nothing is sharded."""
        import numpy as onp

        entries, blobs, mesh_info = [], {}, [None]

        def sharded(x):
            if not _is_device_array(x):
                return False
            sh = getattr(x, "sharding", None)
            try:
                return (sh is not None and len(x.devices()) > 1
                        and not sh.is_fully_replicated)
            except Exception:  # noqa: BLE001 — exotic sharding types
                return False

        def walk(tree):
            if sharded(tree):
                idx = len(entries)
                sh = tree.sharding
                mesh = getattr(sh, "mesh", None)
                if mesh_info[0] is None and mesh is not None:
                    axes = dict(mesh.shape)
                    mesh_info[0] = {"axes": list(axes),
                                    "shape": [int(s)
                                              for s in axes.values()]}
                entries.append({
                    "idx": idx, "shape": [int(d) for d in tree.shape],
                    "dtype": str(tree.dtype),
                    "spec": repr(getattr(sh, "spec", None))})
                devs = sorted(d.id for d in tree.devices())
                ordinal = {d: i for i, d in enumerate(devs)}
                for s in tree.addressable_shards:
                    slices = [
                        [0 if sl.start is None else int(sl.start),
                         int(dim) if sl.stop is None else int(sl.stop)]
                        for sl, dim in zip(s.index, tree.shape)]
                    blobs.setdefault(ordinal[s.device.id], []).append(
                        (idx, slices, onp.asarray(s.data)))
                return (_SHARD_REF, idx)
            if isinstance(tree, tuple):
                return tuple(walk(v) for v in tree)
            if isinstance(tree, list):
                return [walk(v) for v in tree]
            if isinstance(tree, dict):
                return {k: walk(v) for k, v in tree.items()}
            return tree

        snap = walk(snap)
        if not entries:
            return snap, None, {}
        meta = {"mesh": mesh_info[0], "entries": entries,
                "shard_files": [f"shard-{di:03d}.pkl"
                                for di in sorted(blobs)]}
        return snap, meta, blobs

    def _write(self, snap):
        from . import _count
        from . import faults as _faults
        from .. import sharding as _sharding
        from ..telemetry import tracer as _telem

        with _telem.span("checkpoint.write", cat="checkpoint",
                         step=snap["step"],
                         mode="async" if self.async_mode else "sync"):
            self._write_inner(snap, _count, _faults, _sharding)

    def _write_inner(self, snap, _count, _faults, _sharding):
        t0 = time.perf_counter()
        _faults.maybe_fail("checkpoint_write")
        step = snap["step"]
        shard_meta, shard_blobs = None, {}
        if _sharding.sharding_enabled():
            snap, shard_meta, shard_blobs = self._extract_shards(snap)
        content = pickle.dumps(_to_host(snap),
                               protocol=pickle.HIGHEST_PROTOCOL)
        salt = _salt()
        files = {_PAYLOAD: content}
        for di in sorted(shard_blobs):
            files[f"shard-{di:03d}.pkl"] = pickle.dumps(
                shard_blobs[di], protocol=pickle.HIGHEST_PROTOCOL)
        manifest = {
            "format": FORMAT_VERSION, "salt": salt, "step": step,
            "cursor": snap["cursor"],
            "files": {name: {"sha256": _hash(blob, salt),
                             "bytes": len(blob)}
                      for name, blob in files.items()}}
        if shard_meta is not None:
            manifest["sharding"] = shard_meta
        final = self._dir_for(step)
        tmp = os.path.join(
            self.directory,
            f".tmp-ckpt-{step}-{os.getpid()}-{threading.get_ident()}")
        os.makedirs(tmp)
        try:
            for name, blob in files.items():
                if name != _PAYLOAD:
                    # registered fault point: one per-device shard file
                    # of a sharded checkpoint — a fire leaves only the
                    # .tmp-* dir, never a torn visible checkpoint
                    _faults.maybe_fail("checkpoint_shard_write")
                with open(os.path.join(tmp, name), "wb") as f:
                    f.write(blob)
                    f.flush()
                    os.fsync(f.fileno())
            with open(os.path.join(tmp, _MANIFEST), "w") as f:
                json.dump(manifest, f)
                f.flush()
                os.fsync(f.fileno())
            if os.path.exists(final):  # re-saving a step: replace whole
                shutil.rmtree(final)
            os.rename(tmp, final)  # atomic: a crash never exposes a torn dir
        except BaseException:
            shutil.rmtree(tmp, ignore_errors=True)
            raise
        _count("ckpt_saves")
        _count("ckpt_bytes", sum(len(b) for b in files.values()))
        _count("ckpt_write_s", time.perf_counter() - t0)
        if shard_meta is not None:
            _sharding._count("ckpt_sharded_saves")
            _sharding._count("ckpt_shard_files", len(files) - 1)
        self._prune()

    def _prune(self):
        from . import _count

        if self.keep <= 0:
            return
        steps = self.list_steps()
        for step in steps[:-self.keep]:
            shutil.rmtree(self._dir_for(step), ignore_errors=True)
            _count("ckpt_pruned")

    # -- restore -------------------------------------------------------

    def load(self, step=None):
        """The raw payload dict of a checkpoint (the latest valid one
        by default). Raises when none validates. A sharded checkpoint
        is reassembled to full host arrays here — regardless of the
        mesh (or absence of one) in THIS process."""
        if step is None:
            step = self.latest_valid()
            if step is None:
                raise MXNetError(
                    f"no valid checkpoint under {self.directory!r}")
        elif not self.validate(step):
            raise MXNetError(
                f"checkpoint {self._dir_for(step)!r} is missing or "
                "corrupt")
        d = self._dir_for(step)
        with open(os.path.join(d, _PAYLOAD), "rb") as f:
            payload = pickle.load(f)
        with open(os.path.join(d, _MANIFEST)) as f:
            shard_meta = json.load(f).get("sharding")
        if shard_meta is not None:
            payload = self._reassemble(d, payload, shard_meta)
        return payload

    @staticmethod
    def _reassemble(d, payload, meta):
        """Stitch per-device shard files back into full host arrays and
        substitute them for the payload's placeholders. The saved
        global index slices make this mesh-shape agnostic — the
        resharding-on-load half of the sharded-checkpoint contract
        (place back per plan happens in ``restore``)."""
        import numpy as onp

        from .. import sharding as _sharding

        full = {e["idx"]: onp.zeros(tuple(e["shape"]),
                                    dtype=e["dtype"])
                for e in meta["entries"]}
        for fname in meta["shard_files"]:
            with open(os.path.join(d, fname), "rb") as f:
                for idx, slices, arr in pickle.load(f):
                    full[idx][tuple(slice(a, b)
                                    for a, b in slices)] = arr

        def walk(tree):
            if isinstance(tree, tuple):
                if len(tree) == 2 and tree[0] == _SHARD_REF:
                    return full[tree[1]]
                return tuple(walk(v) for v in tree)
            if isinstance(tree, list):
                return [walk(v) for v in tree]
            if isinstance(tree, dict):
                return {k: walk(v) for k, v in tree.items()}
            return tree

        payload = walk(payload)
        _sharding._count("ckpt_sharded_restores")
        ctx = _sharding.current_plan()
        cur = None
        if ctx is not None:
            axes = dict(ctx[1].shape)
            cur = {"axes": list(axes),
                   "shape": [int(s) for s in axes.values()]}
        if cur != meta.get("mesh"):
            # restoring onto a different mesh shape (or none at all):
            # the writer's layout no longer exists — count the reshape
            _sharding._count("ckpt_reshards")
        return payload

    def restore(self, step=None):
        """Restore the latest valid (or given) checkpoint into the
        attached trainer/params/kvstore/PRNG. Returns ``{"step",
        "cursor", "extra"}`` so the caller can reposition its data
        pipeline. Any pending async write is joined first (restoring
        over a half-captured newer state would race the writer)."""
        from . import _count
        from ..telemetry import tracer as _telem

        with _telem.span("checkpoint.restore", cat="checkpoint") as _sp:
            out = self._restore_inner(step, _count)
            _sp.set(step=out["step"])
            return out

    def _restore_inner(self, step, _count):
        self.wait()
        payload = self.load(step)
        if payload.get("params") is not None:
            self._restore_params(payload["params"])
        if payload.get("trainer") is not None and self.trainer is not None:
            self._restore_trainer(self.trainer, payload["trainer"])
        if payload.get("prng") is not None and self.include_prng:
            import jax.numpy as jnp

            from .. import random as _mxrandom

            _mxrandom._GLOBAL_SEED[0] = payload["prng"]["global_seed"]
            _mxrandom._STATE.key = jnp.asarray(payload["prng"]["key"])
        if payload.get("kvstore") is not None and self.kvstore is not None:
            self._restore_kvstore(self.kvstore, payload["kvstore"])
        if payload.get("session_state") is not None and \
                self.session_state is not None:
            self.session_state.restore_state(payload["session_state"])
        self._replace_per_plan()
        _count("ckpt_restores")
        return {"step": payload["step"], "cursor": payload["cursor"],
                "extra": payload.get("extra")}

    def _replace_per_plan(self):
        """Under an active ``sharding.plan_scope``, put the restored
        (host-reassembled, single-device) parameter buffers straight
        back at the plan's layouts — the other half of
        resharding-on-load. Optimizer state re-places itself on the
        next fused step (``FusedShardCfg.place_args``); without a plan
        scope this is a no-op and buffers stay where ``nd.array`` put
        them."""
        from .. import sharding as _sharding

        ctx = _sharding.current_plan()
        if ctx is None:
            return
        params = self._params
        if params is None and self.trainer is not None:
            params = self.trainer._params
        if params is None:
            return
        _sharding.place_params(
            [(p.name, p) for p in params
             if getattr(p, "_ndarray", None) is not None],
            plan=ctx[0], mesh=ctx[1])

    def _restore_params(self, saved):
        params = self._params
        if params is None and self.trainer is not None:
            params = self.trainer._params
        if params is None:
            return
        by_name = {p.name: p for p in params}
        missing = [name for name, _ in saved if name not in by_name]
        if missing:
            raise MXNetError(
                "checkpoint parameters not present in the attached "
                f"group: {missing} (model/trainer mismatch?)")
        from .. import ndarray as nd
        from ..gluon import fused_step as _fs

        launder = _fs.donate_params_enabled()
        for name, val in saved:
            p = by_name[name]
            p._load_init_from(nd.array(val))
            if launder:
                # under MXNET_FUSED_STEP_DONATE param buffers are
                # donated too — same device_put-donation hazard as the
                # states (fused_step.state_adopt)
                import jax.numpy as jnp

                p._ndarray._data = jnp.array(p._ndarray._data,
                                             copy=True)

    @staticmethod
    def _restore_trainer(trainer, payload):
        from ..gluon import fused_step as _fs

        trainer._abandon_speculation()
        # shared walk (fused_step.state_tree_restore): rebuilds the
        # tagged tree with donation-safe (state_adopt'ed) buffers —
        # bitwise resume depends on not donating raw device_put
        # uploads to the fused step
        trainer._states = [_fs.state_tree_restore(s)
                           for s in payload["states"]]
        trainer._states_created = True
        optim = trainer._optimizer
        optim.num_update = payload["num_update"]
        optim.begin_num_update = payload["begin_num_update"]
        optim._index_update_count = dict(payload["index_update_count"])
        trainer._fused_skips_host = payload["fused_skips"]
        scaler_state = payload.get("scaler")
        scaler = getattr(trainer, "_amp_loss_scaler", None)
        if scaler_state is not None and scaler is not None:
            scaler._loss_scale = float(scaler_state["loss_scale"])
            scaler._unskipped = int(scaler_state["unskipped"])
            # the grow schedule rides along: a resumed process whose
            # trainer was constructed with different scaler settings
            # must still replay the ORIGINAL run's episode exactly
            scaler._scale_factor = float(scaler_state["scale_factor"])
            scaler._scale_window = int(scaler_state["scale_window"])
        # device step-state is stale now; re-seed from the restored
        # host values on the next fused step
        trainer._invalidate_fused_state()

    @staticmethod
    def _restore_kvstore(kv, payload):
        from .. import ndarray as nd

        for k, val in payload["values"].items():
            arr = nd.array(val)
            stored = kv._store.get(k)
            if stored is None:
                kv._store[k] = arr
            else:
                stored._data = arr.data.astype(stored.data.dtype)
        states = payload.get("updater_states")
        updater = getattr(kv, "_updater", None)
        if states is not None and updater is not None and \
                hasattr(updater, "set_states"):
            updater.set_states(states)

    # -- lifecycle -----------------------------------------------------

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.wait()
