"""Checkpoint / resume — mid-training persistence of the center variable.

The reference has nothing in-tree (SURVEY.md §5.4: users call
``model.save()`` on the returned Keras model; a dead parameter server loses
the run).  Here the full training state — center params, per-worker local
replicas, optimizer state, rule state (clocks/anchors), epoch counter —
checkpoints through Orbax, so an interrupted distributed run resumes exactly
(bitwise, given the same data order seed).

Saves are asynchronous (``ocp.AsyncCheckpointer``): the host thread returns
as soon as the state is snapshotted, so per-epoch checkpointing stays off
the training path; ``CheckpointManager.wait()`` (called by trainers at the
end of the epoch loop, and implicitly before any restore) flushes the queue.

**Verified publication.**  A step is *published* — visible to restores,
watchers, GC, and the serving tier — only once a ``step_N.manifest.json``
commit record sits next to its directory: per-file sha256 + sizes + step +
run id, written tmp + fsync + ``os.replace`` (+ parent-dir fsync) after the
orbax commit landed.  :func:`verify_checkpoint` checks a published step
against its manifest (``fast`` = existence + sizes, ``full`` = digests);
every restore path verifies before load, renames a failing step aside
(``step_N.corrupt`` + ``checkpoint_quarantined_total``), and falls back to
the newest step that does verify — so a torn write or a flipped bit can
cost at most one checkpoint interval, never the run or the serving fleet.
Orbax directories without a manifest are *unverified* (a crash between the
orbax write and the manifest commit, another process's in-flight save, or a
pre-manifest checkpoint — adopt those explicitly via
:func:`write_manifest`): never restored, never GC'd, never quarantined.
"""

from __future__ import annotations

import hashlib
import json
import os
import threading
from typing import Any, Optional

import jax
import numpy as np

from distkeras_tpu import chaos as _chaos
from distkeras_tpu import telemetry

__all__ = [
    "save_checkpoint", "restore_checkpoint", "restore_center",
    "model_state_worker_mean", "latest_step",
    "checkpoint_num_workers", "CheckpointManager", "CheckpointWatcher",
    "save_data_state", "restore_data_state",
    "manifest_path", "write_manifest", "verify_checkpoint", "verify_failure",
    "quarantine_step", "committed_steps",
]

_CHECKPOINTER = None
_PYTREE_CHECKPOINTER = None


def _checkpointer():
    """Singleton async checkpointer on the current (non-deprecated) Orbax
    API: ``AsyncCheckpointer(StandardCheckpointHandler)`` with explicit
    ``args.StandardSave/StandardRestore`` (the round-1 ``PyTreeCheckpointer``
    is deprecated upstream)."""
    global _CHECKPOINTER
    if _CHECKPOINTER is None:
        import orbax.checkpoint as ocp

        _CHECKPOINTER = ocp.AsyncCheckpointer(ocp.StandardCheckpointHandler())
    return _CHECKPOINTER


def _pytree_checkpointer():
    """Singleton synchronous PyTree checkpointer for the partial
    (PLACEHOLDER) restores — built once, like :func:`_checkpointer`, instead
    of leaking a fresh instance per elastic resume."""
    global _PYTREE_CHECKPOINTER
    if _PYTREE_CHECKPOINTER is None:
        import orbax.checkpoint as ocp

        _PYTREE_CHECKPOINTER = ocp.Checkpointer(ocp.PyTreeCheckpointHandler())
    return _PYTREE_CHECKPOINTER


# ------------------------------------------------------ verified publication

#: (directory, step) pairs whose orbax save has been enqueued but whose
#: manifest has not been published yet.  In-process bookkeeping only — it
#: mirrors exactly the window a real crash would leave on disk (orbax dir
#: without a manifest), so losing it to a crash loses nothing.
_PENDING: list = []
_PENDING_LOCK = threading.Lock()

#: (manifest path) -> (manifest stat, per-file stats) recorded when a step
#: passed a FULL digest verify — skips re-hashing multi-GB state when one
#: resume sequence (worker-count probe, center restore, model-state reduce)
#: re-resolves the same step several times.  A memo hit still stats every
#: file: any size/mtime change since the digests were proven (a republish,
#: or damage landing after the verify) drops the memo and re-hashes.
_VERIFIED: dict = {}


def manifest_path(directory: str, step: int) -> str:
    """The ``step_<n>.manifest.json`` commit record published after the
    orbax save lands.  A plain file, so :func:`committed_steps`'s digit
    parse never mistakes it for a step directory."""
    return os.path.join(os.path.abspath(directory),
                        f"step_{step}.manifest.json")


def _fsync_dir(path: str) -> None:
    """Make a directory entry durable (the rename itself, not just the
    renamed bytes).  Best-effort: not every filesystem lets you open or
    fsync a directory."""
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:
        return
    try:
        os.fsync(fd)
    except OSError:
        pass
    finally:
        os.close(fd)


def _atomic_write_json(path: str, obj) -> None:
    """tmp + fsync + ``os.replace`` + parent-dir fsync: a reader sees the
    old file or the new file, never a torn one — and the new one survives
    power loss once this returns."""
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as fh:
        json.dump(obj, fh, sort_keys=True)
        fh.flush()
        os.fsync(fh.fileno())
    os.replace(tmp, path)
    _fsync_dir(os.path.dirname(path))


def _step_files(step_dir: str) -> list:
    """Every regular file under a step directory, as sorted relative paths
    — the manifest's (and verify's) stable enumeration order."""
    out = []
    for root, dirs, files in os.walk(step_dir):
        dirs.sort()
        for name in sorted(files):
            out.append(os.path.relpath(os.path.join(root, name), step_dir))
    return out


def _sha256_file(path: str):
    h = hashlib.sha256()
    size = 0
    with open(path, "rb") as fh:
        for chunk in iter(lambda: fh.read(1 << 20), b""):
            h.update(chunk)
            size += len(chunk)
    return h.hexdigest(), size


def write_manifest(directory: str, step: int) -> str:
    """Hash a committed ``step_<n>`` directory and publish its commit
    record.  Called automatically as async saves land; call it directly
    only to *adopt* a checkpoint written by an external (pre-manifest)
    writer into the verified set."""
    directory = os.path.abspath(directory)
    step_dir = os.path.join(directory, f"step_{step}")
    files = {}
    with telemetry.trace.span("checkpoint_publish", phase="ckpt",
                              step=int(step)):
        for rel in _step_files(step_dir):
            digest, size = _sha256_file(os.path.join(step_dir, rel))
            files[rel] = {"sha256": digest, "bytes": size}
        from distkeras_tpu.telemetry.flightdeck import correlate

        path = manifest_path(directory, step)
        _atomic_write_json(path, {
            "version": 1,
            "step": int(step),
            "run_id": correlate.run_id(),
            "files": files,
        })
    return path


def _publish(directory: str, step: int) -> None:
    """Publish one landed save: chaos ``ckpt_commit`` site (kill/delay in
    the committed-but-unpublished window), manifest write, then the
    post-publish corruption site (torn/flipped bytes the manifest must
    catch on the next verify)."""
    if _chaos.enabled():
        _chaos.fault("ckpt_commit")
    write_manifest(directory, step)
    if telemetry.enabled():
        telemetry.metrics.counter(
            "checkpoints_published_total",
            help="checkpoint manifests committed (verified-publication record)",
        ).inc()
    if _chaos.enabled():
        step_dir = os.path.join(directory, f"step_{step}")
        _chaos.corrupt_ckpt(
            os.path.join(step_dir, rel) for rel in _step_files(step_dir))


def _publish_pending(purge_missing: bool = False) -> None:
    """Publish manifests for every pending save whose final ``step_<n>``
    directory exists — orbax renames the directory into place only at
    commit, so the listing alone is commit evidence.  ``purge_missing``
    (set after a clean flush) drops entries whose save provably failed."""
    with _PENDING_LOCK:
        entries = list(_PENDING)
    for entry in entries:
        directory, step = entry
        if os.path.isdir(os.path.join(directory, f"step_{step}")):
            with _PENDING_LOCK:
                if entry not in _PENDING:
                    continue  # another thread claimed it
                _PENDING.remove(entry)
            # a raise here (chaos kill_commit, ENOSPC) leaves the step
            # unpublished for good — exactly the on-disk state a real
            # crash in this window leaves behind
            _publish(directory, step)
        elif purge_missing:
            with _PENDING_LOCK:
                if entry in _PENDING:
                    _PENDING.remove(entry)


def wait_until_finished() -> None:
    """Block until every in-flight async save has committed, then publish
    the manifests that make those commits visible."""
    try:
        if _CHECKPOINTER is not None:
            with telemetry.trace.span("checkpoint_flush", phase="ckpt"):
                _CHECKPOINTER.wait_until_finished()
    finally:
        # even when the flush re-raises a failed async save, the saves
        # that DID land still publish (train_with_recovery resumes from
        # them); only a clean flush proves a missing dir means a dead
        # save rather than one still in flight
        _publish_pending()
    _publish_pending(purge_missing=True)


def save_checkpoint(directory: str, state: Any, step: int,
                    force: bool = False) -> str:
    """Write training state under ``directory/step_N`` (async); returns the
    path.  Call :func:`wait_until_finished` before reading it back.

    ``force=True`` overwrites an existing ``step_N`` — the mid-epoch
    (datapipe) save path, where the same step id is re-saved as the block
    cursor advances and finally superseded by the epoch-boundary save.  A
    forced save flushes the async queue first so it cannot race an
    in-flight write to the same path."""
    import orbax.checkpoint as ocp

    directory = os.path.abspath(directory)
    path = os.path.join(directory, f"step_{step}")
    entry = (directory, int(step))
    if not force and os.path.isdir(path) \
            and not os.path.exists(manifest_path(directory, step)):
        # an orbax dir with no manifest is an orphan from a crash between
        # the orbax commit and the manifest publish: nothing will ever
        # restore it, so the re-save of its step overwrites it
        force = True
    # "checkpoint_enqueue" covers only the synchronous part of an async
    # save: the host snapshot plus handing the write to Orbax's thread.
    with telemetry.trace.span("checkpoint_enqueue", phase="ckpt", step=int(step)):
        host_state = jax.tree.map(np.asarray, state)
        if force:
            # the step is being superseded: retract its pending record and
            # its published manifest FIRST, so the stale manifest can never
            # describe (and a reader never verify against) the replacement
            # bytes orbax is about to write
            with _PENDING_LOCK:
                if entry in _PENDING:
                    _PENDING.remove(entry)
            try:
                os.remove(manifest_path(directory, step))
            except FileNotFoundError:
                pass
            wait_until_finished()
        _checkpointer().save(
            path, args=ocp.args.StandardSave(host_state), force=force)
    # orbax's save() waited for every *previous* save internally, so those
    # are committed now — publish their manifests before registering this
    # one (whose manifest lands at the next flush / save)
    with _PENDING_LOCK:
        _PENDING.append(entry)
    _publish_pending()
    if telemetry.enabled():
        telemetry.metrics.counter(
            "checkpoints_saved_total", help="async checkpoint saves enqueued"
        ).inc()
    return path


def data_state_path(directory: str, step: int) -> str:
    """The ``step_<n>_data.json`` sidecar carrying a step's
    :class:`~distkeras_tpu.datapipe.DataState`.  A plain file (no ``step_<n>``
    *directory* name), so :func:`committed_steps`'s digit parse never
    mistakes it for a checkpoint step."""
    return os.path.join(os.path.abspath(directory), f"step_{step}_data.json")


def save_data_state(directory: str, data_state, step: int) -> str:
    """Write the data checkpoint sidecar for ``step`` — synchronous (a few
    hundred bytes), atomic, and durable (tmp + fsync + rename + dir fsync),
    so a crash can never leave a half-written cursor next to a committed
    model step, and power loss cannot un-write one that was reported
    saved."""
    path = data_state_path(directory, step)
    # the model's asynchronous save beside it may not have made the
    # directory yet
    os.makedirs(os.path.dirname(path), exist_ok=True)
    _atomic_write_json(path, data_state.to_json())
    return path


def restore_data_state(directory: str, step: Optional[int] = None):
    """The :class:`~distkeras_tpu.datapipe.DataState` saved with ``step``
    (default: latest), or None — model-only checkpoints (pre-datapipe runs,
    external writers) resume with the legacy epoch-boundary RNG
    fast-forward instead."""
    from distkeras_tpu.datapipe.state import DataState

    if step is None:
        step = latest_step(directory)
        if step is None:
            return None
    path = data_state_path(directory, step)
    if not os.path.exists(path):
        return None
    with open(path, "r", encoding="utf-8") as fh:
        return DataState.from_json(json.load(fh))


def committed_steps(directory: str) -> list:
    """*Published* steps: a ``step_<n>.manifest.json`` commit record next
    to a final ``step_<n>`` directory — readable cross-process with no
    flush.  Orbax dirs without a manifest (in-flight async saves, crashes
    between the orbax write and the manifest commit) and quarantined
    ``step_<n>.corrupt`` renames do not count."""
    directory = os.path.abspath(directory)
    if not os.path.isdir(directory):
        return []
    names = set(os.listdir(directory))
    suffix = ".manifest.json"
    out = []
    for d in names:
        if d.startswith("step_") and d.endswith(suffix):
            num = d[len("step_"):-len(suffix)]
            if num.isdigit() and f"step_{num}" in names:
                out.append(int(num))
    return sorted(out)


def _orbax_step_dirs(directory: str) -> list:
    """Steps with a final orbax dir, manifested or not — the pre-manifest
    commit evidence.  Restores never trust this alone; it exists for the
    recovery paths that must *see* an unpublished step (to avoid colliding
    with or deleting it) without ever loading it."""
    directory = os.path.abspath(directory)
    if not os.path.isdir(directory):
        return []
    return sorted(
        int(d.split("_", 1)[1])
        for d in os.listdir(directory)
        if d.startswith("step_") and d.split("_", 1)[1].isdigit()
    )


def latest_step(directory: str) -> Optional[int]:
    wait_until_finished()  # a step only counts once its async save committed
    steps = committed_steps(directory)
    return steps[-1] if steps else None


def verify_failure(directory: str, step: int,
                   mode: str = "fast") -> Optional[str]:
    """Why ``step`` fails verification against its manifest, or ``None``
    when it passes.  ``fast`` checks every manifested file exists at its
    recorded size (catches torn writes); ``full`` additionally re-hashes
    every file (catches bit flips — sizes intact, digests not).
    ``off`` always passes."""
    if mode not in ("off", "fast", "full"):
        raise ValueError(f"verify mode must be off|fast|full, got {mode!r}")
    if mode == "off":
        return None
    directory = os.path.abspath(directory)
    mpath = manifest_path(directory, step)
    try:
        with open(mpath, "r", encoding="utf-8") as fh:
            manifest = json.load(fh)
        files = manifest["files"]
    except FileNotFoundError:
        return (f"step {step} has no manifest (in-flight save, crashed "
                "publish, or pre-manifest checkpoint)")
    except (ValueError, KeyError, OSError) as e:
        return f"step {step} manifest unreadable: {e}"
    step_dir = os.path.join(directory, f"step_{step}")
    hash_files = mode == "full"
    if hash_files:
        memo = _VERIFIED.get(mpath)
        if memo is not None:
            try:
                st = os.stat(mpath)
                if memo[0] == (st.st_mtime_ns, st.st_size):
                    hash_files = False  # digests proven; stats re-checked below
                else:
                    _VERIFIED.pop(mpath, None)
                    memo = None
            except OSError:
                memo = None
    file_stats = []
    for rel in sorted(files):
        full = os.path.join(step_dir, rel)
        want = files[rel]
        try:
            st = os.stat(full)
        except OSError:
            return f"step {step}: {rel} missing"
        if st.st_size != int(want["bytes"]):
            return (f"step {step}: {rel} is {st.st_size} bytes, "
                    f"manifest says {want['bytes']}")
        if mode == "full" and not hash_files:
            # memo hit: the digests were proven earlier — but only for the
            # bytes as they were THEN; any stat drift since re-hashes
            if (rel, st.st_size, st.st_mtime_ns) not in memo[1]:
                _VERIFIED.pop(mpath, None)
                return verify_failure(directory, step, mode)
        if hash_files:
            digest, _size = _sha256_file(full)
            if digest != want["sha256"]:
                return f"step {step}: {rel} sha256 mismatch"
            file_stats.append((rel, st.st_size, st.st_mtime_ns))
    if hash_files:
        try:
            st = os.stat(mpath)
            _VERIFIED[mpath] = ((st.st_mtime_ns, st.st_size),
                                frozenset(file_stats))
        except OSError:
            pass
    return None


def verify_checkpoint(directory: str, step: int,
                      mode: str = "fast") -> bool:
    """Whether ``step`` passes manifest verification (see
    :func:`verify_failure` for the mode semantics and the reason text)."""
    return verify_failure(directory, step, mode) is None


def quarantine_step(directory: str, step: int, reason: str = "") -> str:
    """Move a corrupt step out of the restorable set: ``step_N`` →
    ``step_N.corrupt`` (suffix-numbered if that name is taken), with its
    manifest and data sidecar renamed alongside for forensics.  The digit
    parse in :func:`committed_steps` never matches the renamed artifacts,
    so quarantine is also un-publication.  Writer-side only — serving
    replicas reject and keep polling instead (they don't own the dir)."""
    directory = os.path.abspath(directory)
    src = os.path.join(directory, f"step_{step}")
    dst = src + ".corrupt"
    n = 0
    while os.path.exists(dst) or os.path.exists(dst + ".manifest.json"):
        n += 1
        dst = f"{src}.corrupt.{n}"
    if os.path.isdir(src):
        os.replace(src, dst)
    mpath = manifest_path(directory, step)
    _VERIFIED.pop(mpath, None)
    try:
        os.replace(mpath, dst + ".manifest.json")
    except FileNotFoundError:
        pass
    try:
        os.replace(data_state_path(directory, step), dst + "_data.json")
    except FileNotFoundError:
        pass
    _fsync_dir(directory)
    if telemetry.enabled():
        telemetry.metrics.counter(
            "checkpoint_quarantined_total",
            help="corrupt checkpoint steps renamed aside (step_N.corrupt)",
        ).inc()
        # the reason lands in the trace (spans carry attrs; there is no
        # instant-event API) so a postmortem can see WHAT failed, not
        # just that something did
        with telemetry.trace.span("checkpoint_quarantine", phase="ckpt",
                                  step=int(step), reason=reason[:200]):
            pass
    return dst


def _resolve_verified(directory: str, step: Optional[int],
                      mode: str = "full") -> int:
    """The step a restore may actually load: verify first; quarantine a
    corrupt step and fall back to the newest one that verifies.  An
    explicitly requested step without a manifest raises instead of
    falling back — it may be another process's in-flight save (never
    rename it) or a legacy checkpoint (adopt via :func:`write_manifest`)."""
    wait_until_finished()
    directory = os.path.abspath(directory)
    if step is not None:
        reason = verify_failure(directory, step, mode)
        if reason is None:
            return int(step)
        if not os.path.exists(manifest_path(directory, step)):
            raise FileNotFoundError(
                f"cannot restore unverified step under {directory}: {reason}")
        quarantine_step(directory, step, reason)
    while True:
        steps = committed_steps(directory)
        if not steps:
            raise FileNotFoundError(
                f"no verified checkpoints under {directory}")
        newest = steps[-1]
        reason = verify_failure(directory, newest, mode)
        if reason is None:
            return newest
        quarantine_step(directory, newest, reason)


class CheckpointWatcher:
    """Newest-step watcher over a checkpoint directory — the train→serve
    bridge.  ``poll()`` returns the newest *verified* step the first time
    it is seen, ``None`` otherwise.

    Built on :func:`committed_steps` (manifest listing = commit record),
    NOT :func:`latest_step`: the latter flushes *this* process's async save
    queue, which is meaningless — and wrong to wait on — when the trainer
    writing the checkpoints is a different process.  An orbax directory
    whose manifest has not been published yet (an in-flight async save, or
    a crash between the orbax write and the manifest commit) is invisible
    here by construction, and a published step must additionally pass a
    ``fast`` size verify before it is surfaced — a corrupt newest step is
    skipped (older new steps still surface), never returned and never
    touched (quarantine is the writer's job).  With ``start_after``
    omitted, the watcher baselines at the newest step already on disk at
    construction, so only steps committed *afterwards* fire (a serving
    replica that just loaded step N must not be told to hot-swap to step
    N).  Pass ``start_after=-1`` to see every committed step including
    pre-existing ones."""

    def __init__(self, directory: str,
                 start_after: Optional[int] = None):
        self.directory = directory
        if start_after is None:
            steps = committed_steps(directory)
            start_after = steps[-1] if steps else -1
        self.last_step = int(start_after)

    def poll(self) -> Optional[int]:
        """The newest verified step if it is newer than anything reported
        before, else ``None``.  Intermediate steps are skipped on purpose:
        a serving fleet swaps to the freshest params, not through history."""
        for step in reversed(committed_steps(self.directory)):
            if step <= self.last_step:
                return None
            if verify_failure(self.directory, step, "fast") is None:
                self.last_step = step
                return step
            # corrupt (or mid-rewrite): leave last_step alone so a later
            # poll re-checks; fast mode is stat-only, so re-checks are cheap
        return None


def restore_checkpoint(directory: str, step: Optional[int] = None,
                       like: Any = None, verify: str = "full") -> Any:
    """Load training state; ``like`` (a template pytree, e.g. a freshly built
    TrainState) restores exact structure/dtypes and device placement.

    Verifies before load (default ``full`` — a bit flip preserves sizes, so
    only digests prove the bytes): a corrupt step is quarantined and the
    newest verified one loads instead; ``verify="off"`` restores blind
    (external checkpoints without manifests)."""
    import orbax.checkpoint as ocp

    path = _step_path(directory, step, verify)
    template = jax.tree.map(np.asarray, like) if like is not None else None
    restored = _checkpointer().restore(
        path, args=ocp.args.StandardRestore(template)
    )
    if like is not None:
        # re-place on the same shardings as the template
        return jax.tree.map(
            lambda tpl, val: jax.device_put(val, tpl.sharding)
            if hasattr(tpl, "sharding")
            else val,
            like,
            restored,
        )
    return restored


def _step_path(directory: str, step: Optional[int],
               verify: str = "full") -> str:
    """Resolve the directory a restore will read — verified (quarantine +
    newest-verified fallback, see :func:`_resolve_verified`) unless the
    caller opted out with ``verify="off"``."""
    if verify == "off":
        wait_until_finished()
        if step is None:
            step = latest_step(directory)
            if step is None:
                raise FileNotFoundError(f"no checkpoints under {directory}")
    else:
        step = _resolve_verified(directory, step, verify)
    return os.path.join(os.path.abspath(directory), f"step_{step}")


def _metadata_tree(path: str) -> dict:
    meta = _checkpointer().metadata(path)
    tree = getattr(meta, "item_metadata", meta)
    tree = getattr(tree, "tree", tree)
    if not isinstance(tree, dict):
        # the getattr chain above tracks Orbax's metadata API (validated
        # against orbax-checkpoint 0.11.x); a release that reshapes it again
        # should fail here by name, not with a KeyError downstream
        raise RuntimeError(
            "could not read the checkpoint metadata tree as a dict (got "
            f"{type(tree).__name__}) — the installed orbax-checkpoint "
            "version exposes an unexpected metadata layout; "
            "distkeras_tpu.checkpoint expects the 0.11.x "
            "item_metadata/.tree API"
        )
    return tree


def restore_center(
    directory: str, step: Optional[int] = None,
    include_model_state: bool = True,
) -> dict:
    """Partial restore for elastic resume: only the center variable, its
    rule state, the model state, and the epoch counter leave disk; the
    per-worker subtrees (local replicas, optimizer state, rule locals,
    rngs) — ~3N x the model size at N workers — restore as Orbax
    placeholders, i.e. are never read.

    ``include_model_state=False`` additionally placeholders the per-worker
    ``[N, ...]`` model-state stack — pair with
    :func:`model_state_worker_mean`, which reduces that stack leaf by leaf
    instead of materialising all of it at once."""
    import orbax.checkpoint as ocp

    path = _step_path(directory, step)
    tree = _metadata_tree(path)
    keep = ("center_params", "center_rule", "epoch")
    if include_model_state:
        keep = keep + ("model_state",)

    def template_for(key, sub):
        if key in keep:
            return jax.tree.map(
                lambda m: jax.ShapeDtypeStruct(tuple(m.shape), m.dtype), sub
            )
        return jax.tree.map(lambda m: ocp.PLACEHOLDER, sub)

    template = {k: template_for(k, v) for k, v in tree.items()}
    # PLACEHOLDER is a PyTree-handler feature (the Standard handler rejects
    # it); both handlers share the on-disk format, so reading a
    # StandardSave checkpoint through PyTreeRestore is exact.
    restored = _pytree_checkpointer().restore(
        path, args=ocp.args.PyTreeRestore(item=template)
    )
    return {k: restored[k] for k in keep}


def worker_mean(x: np.ndarray) -> np.ndarray:
    """Mean over the leading (workers) axis with resume-grade dtype care:
    accumulate in float64 (bf16 leaves don't round twice), round integer
    leaves to nearest instead of truncating."""
    x = np.asarray(x)
    m = x.astype(np.float64).mean(axis=0)
    if np.issubdtype(x.dtype, np.integer):
        m = np.rint(m)
    return m.astype(x.dtype)


def model_state_worker_mean(
    directory: str, step: Optional[int] = None,
    host_bytes_budget: int = 256 * 1024**2,
):
    """Collapse the checkpointed per-worker ``[N_old, ...]`` model-state
    stack to its worker mean WITHOUT materialising the whole stack on host.

    Elastic resume at a new worker count needs only the mean (the same
    semantic ``sync_model_state`` applies at every commit), but a naive
    restore reads all ``N_old x`` model-state bytes into one host tree —
    for large stateful models exactly the host spike the sharded training
    path avoids.  Instead leaves restore in groups whose combined stack
    size stays under ``host_bytes_budget`` (every other array in the
    checkpoint is an Orbax PLACEHOLDER, i.e. never read) and reduce
    immediately, bounding peak host memory without paying one serial
    restore round-trip per leaf on deeply-stateful models (asserted by the
    restore-spy test in tests/test_elastic.py)."""
    import orbax.checkpoint as ocp
    from jax import tree_util as jtu

    path = _step_path(directory, step)
    tree = _metadata_tree(path)
    sub = tree.get("model_state", {})
    meta_leaves, treedef = jtu.tree_flatten(sub)
    others_placeholder = {
        k: jax.tree.map(lambda m: ocp.PLACEHOLDER, v)
        for k, v in tree.items() if k != "model_state"
    }
    # greedy grouping: combined bytes per restore <= budget (single
    # over-budget leaves still restore alone — that bound is irreducible)
    groups, cur, cur_bytes = [], [], 0
    for i, m in enumerate(meta_leaves):
        nbytes = int(np.prod(m.shape, dtype=np.int64)) * np.dtype(m.dtype).itemsize
        if cur and cur_bytes + nbytes > host_bytes_budget:
            groups.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += nbytes
    if cur:
        groups.append(cur)
    out = [None] * len(meta_leaves)
    for group in groups:
        live = set(group)
        sub_tpl = jtu.tree_unflatten(treedef, [
            jax.ShapeDtypeStruct(tuple(m.shape), m.dtype) if j in live
            else ocp.PLACEHOLDER
            for j, m in enumerate(meta_leaves)
        ])
        restored = _pytree_checkpointer().restore(
            path,
            args=ocp.args.PyTreeRestore(
                item=dict(others_placeholder, model_state=sub_tpl)
            ),
        )
        flat = jtu.tree_flatten(restored["model_state"])[0]
        for i in group:
            out[i] = worker_mean(flat[i])
    return jtu.tree_unflatten(treedef, out)


def checkpoint_num_workers(directory: str, step: Optional[int] = None) -> int:
    """Worker count a checkpoint was written at: the leading dim of its
    per-worker ``rng`` leaf, read from array METADATA only (no tensor data
    leaves disk) — the cheap probe behind elastic resume."""
    tree = _metadata_tree(_step_path(directory, step))
    return int(tree["rng"].shape[0])


class CheckpointManager:
    """Every-N-epochs checkpointing hook used by trainers (``checkpoint_dir``
    + ``checkpoint_every`` kwargs)."""

    def __init__(self, directory: str, every: int = 1, keep: int = 3):
        self.directory = os.path.abspath(directory)
        self.every = max(1, int(every))
        self.keep = keep
        self._saved: set[int] = set()
        # steps whose latest save is a mid-epoch (partial) one: their
        # epoch-boundary save must overwrite (force=True), and their stale
        # cursor sidecar must go when the boundary save supersedes it
        self._partial: set[int] = set()
        os.makedirs(self.directory, exist_ok=True)

    def _is_partial(self, step: int) -> bool:
        """Whether ``step``'s latest save is a mid-epoch one — from this
        manager's memory, or from the on-disk cursor sidecar (sidecar writes
        are synchronous, so a resumed process sees a killed run's partial
        step even while its async model save is still uncommitted)."""
        if step in self._partial:
            return True
        ds = restore_data_state(self.directory, step)
        return ds is not None and int(ds.block_cursor) > 0

    def maybe_save(self, state: Any, epoch: int,
                   data_state=None) -> Optional[str]:
        if (epoch + 1) % self.every:
            return None
        step = epoch + 1
        path = save_checkpoint(self.directory, state, step,
                               force=self._is_partial(step))
        if data_state is not None:
            save_data_state(self.directory, data_state, step)
        else:
            # boundary save without a DataState supersedes a mid-epoch one:
            # drop any stale cursor so resume doesn't skip blocks
            try:
                os.remove(data_state_path(self.directory, step))
            except FileNotFoundError:
                pass
        self._partial.discard(step)
        self._saved.add(step)
        self._gc()
        return path

    def save_partial(self, state: Any, epoch: int, data_state) -> str:
        """Mid-epoch save: model state plus the :class:`DataState` cursor
        marking how far into ``epoch``'s block sequence the run got.  Saved
        under the step the epoch-boundary save will later claim
        (``epoch + 1``) and re-saved in place (``force=True``) as the cursor
        advances — resume always sees one coherent (state, cursor) pair."""
        step = epoch + 1
        path = save_checkpoint(self.directory, state, step, force=True)
        save_data_state(self.directory, data_state, step)
        self._partial.add(step)
        self._saved.add(step)
        self._gc()
        return path

    def restore_data_state(self, step: Optional[int] = None):
        return restore_data_state(self.directory, step)

    def wait(self) -> None:
        """Flush in-flight async saves (end of the trainer epoch loop)."""
        wait_until_finished()
        # everything initiated is now committed: apply the keep policy
        # exactly (collects the predecessor whose deletion _gc deferred
        # while its successor was in flight)
        self._gc()

    def _gc(self) -> None:
        # Only PUBLISHED steps (manifest + final step_ dir on disk) are gc
        # candidates.  Counting the in-flight newest save toward ``keep``
        # would, at keep=1, delete the only restorable checkpoint while the
        # new one is still writing — a crash in that window leaves zero
        # restorable checkpoints.  An in-flight (or crashed-publish) step
        # has no manifest yet, so excluding it both protects it and defers
        # deleting its predecessor until it lands; quarantined
        # ``step_N.corrupt`` renames fail the digit parse entirely and are
        # kept for forensics.  The manifest goes FIRST (un-publication),
        # so no reader can resolve a step whose bytes are mid-deletion.
        import shutil

        committed = committed_steps(self.directory)
        for s in committed[: -self.keep] if self.keep else []:
            self._saved.discard(s)
            self._partial.discard(s)
            try:
                os.remove(manifest_path(self.directory, s))
            except FileNotFoundError:
                pass
            shutil.rmtree(os.path.join(self.directory, f"step_{s}"), ignore_errors=True)
            try:
                os.remove(data_state_path(self.directory, s))
            except FileNotFoundError:
                pass

    def latest(self) -> Optional[int]:
        self.wait()  # flush + exact keep policy before reading the record
        return latest_step(self.directory)

    def latest_verified(self, mode: str = "full") -> Optional[int]:
        """The newest step whose bytes provably match their manifest —
        what resume pins: corrupt steps found on the way are quarantined
        (with their fate counted), so a crash that tore the newest
        checkpoint costs one checkpoint interval, not the run.  ``None``
        when nothing verifiable exists."""
        self.wait()
        try:
            return _resolve_verified(self.directory, None, mode)
        except FileNotFoundError:
            return None

    def saved_worker_count(self, step: Optional[int] = None) -> int:
        return checkpoint_num_workers(self.directory, step)

    def restore_center(
        self, step: Optional[int] = None, include_model_state: bool = True,
    ) -> dict:
        return restore_center(self.directory, step, include_model_state)

    def model_state_worker_mean(
        self, step: Optional[int] = None,
        host_bytes_budget: int = 256 * 1024**2,
    ):
        return model_state_worker_mean(self.directory, step, host_bytes_budget)

    def restore(self, like: Any = None, step: Optional[int] = None,
                verify: str = "full") -> Any:
        return restore_checkpoint(self.directory, step, like, verify)
