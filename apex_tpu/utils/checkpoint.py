"""Sharded, async, multi-host-ready checkpointing over Orbax.

The reference's checkpoint story is single-process ``state_dict``
pickling (ref: apex/amp/frontend.py:428-454 amp scaler serialization,
examples/imagenet/main_amp.py --resume flow); the flax-bytes helpers in
``examples/imagenet/main_amp.py`` mirror that path.  This module is the
TPU-native upgrade the reference never needed: under ``jax.sharding``
every process owns only its shard of the params/optimizer state, so a
checkpoint must be written collectively — Orbax's TensorStore backend
writes each shard from its owning host and restores with any (possibly
different) target sharding, enabling elastic resume across mesh shapes.

Semantics preserved from the amp flow:

* precision portability — when masters exist they are saved (fp32), and
  model params are re-cast from them on restore (the O2/O5 state-dict
  hook, ref: apex/amp/_initialize.py:133-142);
* the scaler state rides along via ``AmpOptimizer.state_dict`` exactly
  as ``amp.state_dict()`` does;
* ``save`` is asynchronous: the training loop continues while shards
  flush (call ``wait()``/``close`` — or rely on the context manager —
  before exiting).

New for the resilience layer (:mod:`apex_tpu.resilience`): checkpoint
**integrity**.  A preempted or crashed run leaves garbage on disk — a
step dir killed before its commit marker, or payload files torn
mid-flush — and a restore that trips over it must not take the run
down.  :meth:`CheckpointManager.latest_valid_step` spots structural
garbage cheaply; :meth:`CheckpointManager.restore` (``step=None``)
additionally survives deep corruption by falling back step-by-step to
the newest checkpoint that actually restores, logging/emitting what was
skipped (``ckpt_skipped`` / ``ckpt_gc`` ``resilience`` events into an
optional ``sink``) and moving the garbage out of the way — structural
trash deleted, torn-restore steps quarantined as ``<step>.corrupt`` —
so it cannot shadow good steps forever.  An explicitly requested
missing step raises a
``FileNotFoundError`` naming the directory and the available steps —
not a raw Orbax traceback.
"""
from __future__ import annotations

import os
import shutil
from typing import Any, List, Optional, Tuple

import jax

from .. import amp as _amp
from .log_util import get_logger

#: Orbax's atomic-commit marker: written last, so its absence means the
#: step dir never finished (or was tampered with) — never restore it.
_FINALIZE_MARKER = "_CHECKPOINT_METADATA"


class CheckpointFormatMismatch(RuntimeError):
    """The checkpoint's master-weight layout does not match the restore
    template: one side is the persistent packed pipeline's
    ``PackedMasters`` flat buffers, the other the per-leaf master tree.
    Raised INSTEAD of letting Orbax fail on an opaque tree-structure
    mismatch (which the integrity fallback would then mistake for a
    torn payload and quarantine a perfectly good checkpoint).  Re-run
    with the matching mode — ``AmpOptimizer(pipeline=True)`` packs, the
    default steps per leaf — or re-save under the new one."""


def _manager(directory: str, keep: int):
    import orbax.checkpoint as ocp

    return ocp.CheckpointManager(
        directory,
        options=ocp.CheckpointManagerOptions(
            max_to_keep=keep, create=True, enable_async_checkpointing=True),
    )


def _fs_steps(directory: str) -> List[int]:
    """Numeric step dirs actually on disk (tmp dirs from a killed async
    save carry an ``.orbax-checkpoint-tmp`` suffix and don't parse)."""
    try:
        return sorted(int(n) for n in os.listdir(directory)
                      if n.isdigit()
                      and os.path.isdir(os.path.join(directory, n)))
    except (FileNotFoundError, NotADirectoryError):
        return []


def _step_integrity(step_dir: str) -> Tuple[bool, str]:
    """Cheap structural validity of one Orbax step dir: finalize marker
    present, every item subdir non-empty, the Standard/Json item
    metadata files in place.  Catches kill-before-commit and gross
    tampering; torn payload *contents* are only caught by the restore
    attempt itself (see :meth:`CheckpointManager.restore`)."""
    if not os.path.isfile(os.path.join(step_dir, _FINALIZE_MARKER)):
        return False, "unfinalized (no _CHECKPOINT_METADATA)"
    items = [n for n in os.listdir(step_dir)
             if os.path.isdir(os.path.join(step_dir, n))]
    if not items:
        return False, "no checkpoint items"
    for item in items:
        if not os.listdir(os.path.join(step_dir, item)):
            return False, f"empty item {item!r}"
    state_meta = os.path.join(step_dir, "state", "_METADATA")
    if os.path.isdir(os.path.dirname(state_meta)) \
            and not os.path.isfile(state_meta):
        return False, "state item missing _METADATA"
    meta_file = os.path.join(step_dir, "meta", "metadata")
    if os.path.isdir(os.path.dirname(meta_file)) and (
            not os.path.isfile(meta_file)
            or os.path.getsize(meta_file) == 0):
        return False, "meta item missing/empty"
    return True, ""


def latest_valid_step(directory: str) -> Optional[int]:
    """Newest step under ``directory`` that passes the structural
    integrity scan (None if there is none).  Module-level twin of
    :meth:`CheckpointManager.latest_valid_step` for callers that only
    need to *decide whether* to resume."""
    if "://" in directory:
        raise ValueError("integrity scan requires a filesystem path; "
                         "use CheckpointManager for URI destinations")
    for step in reversed(_fs_steps(directory)):
        ok, _ = _step_integrity(os.path.join(directory, str(step)))
        if ok:
            return step
    return None


class CheckpointManager:
    """``with CheckpointManager(dir) as mgr: mgr.save(step, ...)``.

    Thin policy layer over ``orbax.checkpoint.CheckpointManager`` that
    knows the amp layout (masters / scalers / model-dtype writeback).
    ``extra`` carries any additional pytrees (batch_stats, data-loader
    cursors, ...) — they are restored by structure.

    ``sink`` (optional, any :class:`apex_tpu.monitor.Sink`) receives
    ``resilience`` events when restore has to skip or GC a damaged
    step, so integrity fallbacks land in the same JSONL as the rest of
    the run's telemetry.
    """

    def __init__(self, directory: str, keep: int = 3, sink=None):
        # Only absolutize plain filesystem paths — abspath would mangle
        # URI-scheme destinations (gs://b/... -> <cwd>/gs:/b/...).
        if "://" not in directory:
            directory = os.path.abspath(directory)
        self.directory = directory
        self._keep = int(keep)
        self._sink = sink
        self._log = get_logger(__name__)
        # Sweep BEFORE Orbax opens: a structurally-invalid step dir
        # left by a dead process would otherwise sit in Orbax's step
        # list, where it silently vetoes any re-save of that step
        # number (save() returns False) while never being restorable.
        if self._fs_backed():
            self._sweep_invalid()
        self._mgr = _manager(directory, keep)

    def _sweep_invalid(self) -> None:
        """Quarantine structurally-invalid step dirs as
        ``<step>.corrupt`` at open (process 0 only under multihost;
        rename keeps the payload for a post-mortem while freeing the
        step number).  Assumes the single-writer model this module is
        built on: no *other* manager may have an async save in flight
        on this directory at open time (a step dir is briefly
        marker-less mid-finalize).  Tolerant of rename races: a
        concurrently swept dir is simply gone."""
        if jax.process_index() != 0:
            return
        for s in _fs_steps(self.directory):
            step_dir = os.path.join(self.directory, str(s))
            ok, reason = _step_integrity(step_dir)
            if ok:
                continue
            try:
                dst = step_dir + ".corrupt"
                shutil.rmtree(dst, ignore_errors=True)
                os.rename(step_dir, dst)
            except OSError:
                continue
            self._log.warning(
                "checkpoint step %d in %s quarantined at open: %s",
                s, self.directory, reason)
            self._emit("ckpt_quarantined", step=s, reason=reason,
                       directory=self.directory)

    # -- integrity surface ---------------------------------------------------

    def _fs_backed(self) -> bool:
        return "://" not in self.directory

    def available_steps(self) -> List[int]:
        """Steps present on disk (or known to Orbax for URI backends),
        regardless of validity."""
        if self._fs_backed():
            return _fs_steps(self.directory)
        return sorted(self._mgr.all_steps())

    def latest_valid_step(self) -> Optional[int]:
        """Newest step passing the structural integrity scan — the step
        ``restore(step=None)`` will try first.  Falls back to Orbax's
        own ``latest_step`` on URI backends (no local scan possible)."""
        if not self._fs_backed():
            return self._mgr.latest_step()
        return latest_valid_step(self.directory)

    def _emit(self, name: str, value=None, step=None, **attrs) -> None:
        from ..monitor.events import emit_resilience

        emit_resilience(self._sink, name, value=value, step=step,
                        **attrs)

    def _reopen(self) -> None:
        """Recreate the Orbax manager after step dirs were removed
        behind its back (its step cache must not resurrect them)."""
        self._mgr.close()
        self._mgr = _manager(self.directory, self._keep)

    # -- save ---------------------------------------------------------------

    def save(self, step: int, params: Any, amp_opt=None, amp_state=None,
             extra: Optional[dict] = None) -> None:
        """Async-save a training state at ``step``.

        With amp: the fp32 masters are written instead of the cast
        params (precision portability); scaler scalars ride in the
        ``amp`` entry.  Without amp: ``params`` is written as-is.
        """
        import orbax.checkpoint as ocp

        if amp_state is not None and amp_state.master_params is not None:
            tree = {"params": amp_state.master_params,
                    "inner_state": amp_state.inner_state}
        else:
            tree = {"params": params,
                    "inner_state": None if amp_state is None
                    else amp_state.inner_state}
        items = {"state": ocp.args.StandardSave(tree)}
        meta = {"step": int(step)}
        if amp_state is not None and amp_state.master_params is not None:
            # Record the master layout so a mixed-mode restore fails
            # with a clear CheckpointFormatMismatch, not an opaque
            # Orbax structure error (absent key = pre-pipeline
            # checkpoint = per-leaf masters).
            meta["packed_masters"] = hasattr(
                amp_state.master_params, "to_model")
        if amp_opt is not None and amp_state is not None:
            meta["amp"] = amp_opt.state_dict(amp_state)
        items["meta"] = ocp.args.JsonSave(meta)
        if extra:
            items["extra"] = ocp.args.StandardSave(extra)
        accepted = self._mgr.save(step, args=ocp.args.Composite(**items))
        if accepted is False:
            # Orbax skips (returns False) instead of raising when the
            # step number already exists on disk — a silent drop here
            # would let a clean-exit marker claim durability the store
            # doesn't have.
            raise RuntimeError(
                f"checkpoint save of step {step} under "
                f"{self.directory} was declined by Orbax (step already "
                f"on disk?); existing steps: {self.available_steps()}")

    # -- restore ------------------------------------------------------------

    def latest_step(self) -> Optional[int]:
        return self._mgr.latest_step()

    def restore(self, params: Any, amp_opt=None, amp_state=None,
                extra: Optional[dict] = None, step: Optional[int] = None,
                gc_invalid: bool = True):
        """Restore into the shapes/shardings of the given templates.

        Returns ``(params, amp_state, extra, step)`` — params in the
        model dtype (re-cast from restored masters when amp is active),
        restored onto whatever sharding the template arrays carry (a
        different mesh than the one saved from is fine).

        With ``step=None`` the restore is **integrity-checked**: steps
        failing the structural scan are skipped outright, and a
        structurally-sound step whose payload is torn (restore raises)
        falls back to the next-newest candidate — each skip logged and
        emitted as a ``ckpt_skipped`` event, and (``gc_invalid=True``)
        the damaged dirs moved out of the way so they never shadow a
        good step again (structural garbage deleted, restore failures
        quarantined as ``<step>.corrupt``).  An explicit ``step`` that
        does not exist raises a
        ``FileNotFoundError`` naming this directory and the available
        steps.
        """
        if step is not None:
            available = self.available_steps()
            if step not in available:
                raise FileNotFoundError(
                    f"checkpoint step {step} not found under "
                    f"{self.directory}; available steps: "
                    f"{available if available else 'none'}")
            return self._restore_step(step, params, amp_opt, amp_state,
                                      extra)
        if not self._fs_backed():
            # URI backend: no local integrity scan; plain Orbax path.
            step = self.latest_step()
            if step is None:
                raise FileNotFoundError(
                    f"no checkpoint found under {self.directory}")
            return self._restore_step(step, params, amp_opt, amp_state,
                                      extra)

        skipped: List[Tuple[int, str]] = []
        candidates: List[int] = []
        for s in sorted(_fs_steps(self.directory), reverse=True):
            ok, reason = _step_integrity(
                os.path.join(self.directory, str(s)))
            if ok:
                candidates.append(s)
            else:
                skipped.append((s, reason))
        result = None
        for s in candidates:
            try:
                result = self._restore_step(s, params, amp_opt,
                                            amp_state, extra)
                break
            except CheckpointFormatMismatch:
                # a good checkpoint in the OTHER master layout is not
                # damage — never quarantine it, surface the real error
                raise
            except Exception as e:  # apex-lint: disable=APX202 -- deep-restore fallback: ANY torn-payload error must become a skip entry (recorded + ckpt_skipped event upstream), never a crash
                skipped.append(
                    (s, f"restore failed: {type(e).__name__}: "
                        f"{str(e)[:200]}"))
        if result is None:
            detail = "".join(f"\n  step {s}: {r}" for s, r in skipped)
            raise FileNotFoundError(
                f"no valid checkpoint found under {self.directory}"
                + (f"; skipped:{detail}" if skipped else ""))
        restored_step = result[3]
        # Report/GC only what the fallback actually stepped over —
        # steps older than the one restored are not in the way.
        stale = sorted((s, r) for s, r in skipped if s > restored_step)
        for s, reason in stale:
            self._log.warning(
                "checkpoint step %d in %s skipped: %s (restored %d)",
                s, self.directory, reason, restored_step)
            self._emit("ckpt_skipped", step=s, reason=reason,
                       restored_step=restored_step,
                       directory=self.directory)
        if gc_invalid and stale:
            # Structural garbage (no commit marker / empty items) is
            # incomplete by construction — delete it.  A structurally
            # sound step whose *restore* failed could in principle be a
            # transient host error rather than a torn payload, so it is
            # quarantined (renamed ``<step>.corrupt``) instead of
            # destroyed — out of the step namespace, but recoverable
            # for a post-mortem.
            removed, quarantined = [], []
            for s, reason in stale:
                src = os.path.join(self.directory, str(s))
                if reason.startswith("restore failed"):
                    dst = src + ".corrupt"
                    shutil.rmtree(dst, ignore_errors=True)
                    os.rename(src, dst)
                    quarantined.append(s)
                else:
                    shutil.rmtree(src, ignore_errors=True)
                    removed.append(s)
            self._log.warning(
                "garbage-collected %d invalid checkpoint step(s): "
                "deleted %s, quarantined as .corrupt %s",
                len(stale), removed, quarantined)
            self._emit("ckpt_gc", value=len(stale),
                       steps=[s for s, _ in stale],
                       removed=removed, quarantined=quarantined,
                       directory=self.directory)
            self._reopen()
        return result

    def _restore_step(self, step: int, params: Any, amp_opt=None,
                      amp_state=None, extra: Optional[dict] = None):
        import orbax.checkpoint as ocp

        # Meta restores first, alone: it carries the master-layout flag
        # the format pre-check needs, and fetching it once here (reused
        # below, not re-restored in the Composite) keeps the amp path
        # at a single storage round-trip for the JSON item.
        meta = self._mgr.restore(
            step, args=ocp.args.Composite(
                meta=ocp.args.JsonRestore()))["meta"]
        if amp_state is not None and amp_state.master_params is not None:
            # Format pre-check before the full restore: a packed-vs-
            # leafwise master mismatch must raise the dedicated error,
            # not an Orbax structure failure the integrity fallback
            # would quarantine as a torn payload.
            want_packed = hasattr(amp_state.master_params, "to_model")
            have_packed = bool(meta.get("packed_masters", False))
            if want_packed != have_packed:
                raise CheckpointFormatMismatch(
                    f"checkpoint step {step} under {self.directory} "
                    f"stores {'packed' if have_packed else 'per-leaf'} "
                    f"master weights but the restore template is "
                    f"{'packed' if want_packed else 'per-leaf'} — the "
                    "persistent-pipeline mode changed between save and "
                    "restore.  Re-run with the matching mode "
                    "(AmpOptimizer(pipeline=True) packs, the default "
                    "steps per leaf) or re-save the checkpoint.")
            tree = {"params": amp_state.master_params,
                    "inner_state": amp_state.inner_state}
        else:
            tree = {"params": params,
                    "inner_state": None if amp_state is None
                    else amp_state.inner_state}
        items = {"state": ocp.args.StandardRestore(tree)}
        if extra:
            items["extra"] = ocp.args.StandardRestore(extra)
        out = self._mgr.restore(step, args=ocp.args.Composite(**items))
        tree = out["state"]
        new_extra = out.get("extra") if extra else None

        if amp_state is not None and amp_state.master_params is not None:
            masters = tree["params"]
            if hasattr(masters, "to_model"):
                # Persistent packed pipeline mode: masters are a
                # PackedMasters (flat fp32 buffers + static layout) —
                # assemble the model-dtype params from the packed
                # buffers instead of a leafwise re-cast.
                new_params = masters.to_model(params)
            else:
                new_params = _amp.restore_dtypes(masters, params)
            amp_state = amp_state._replace(
                master_params=masters, inner_state=tree["inner_state"])
        else:
            new_params = tree["params"]
            if amp_state is not None:
                amp_state = amp_state._replace(
                    inner_state=tree["inner_state"])
        if amp_opt is not None and amp_state is not None \
                and "amp" in meta:
            amp_state = amp_opt.load_state_dict(amp_state, meta["amp"])
        return new_params, amp_state, new_extra, int(meta["step"])

    # -- lifecycle ----------------------------------------------------------

    def wait(self) -> None:
        """Block until pending async saves are durable."""
        self._mgr.wait_until_finished()

    def close(self) -> None:
        self.wait()
        self._mgr.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


def save_checkpoint(directory: str, step: int, params, amp_opt=None,
                    amp_state=None, extra=None, keep: int = 3) -> None:
    """One-shot synchronous convenience wrapper."""
    with CheckpointManager(directory, keep=keep) as mgr:
        mgr.save(step, params, amp_opt, amp_state, extra)


def load_checkpoint(directory: str, params, amp_opt=None, amp_state=None,
                    extra=None, step: Optional[int] = None):
    """One-shot restore; see :meth:`CheckpointManager.restore`."""
    with CheckpointManager(directory) as mgr:
        return mgr.restore(params, amp_opt, amp_state, extra, step)


# Re-exported under jax.distributed multihost usage: every process must
# call save/restore collectively (Orbax coordinates via the JAX
# distributed client); no extra wiring needed here.
