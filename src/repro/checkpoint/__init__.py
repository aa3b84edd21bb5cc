"""Checkpointing: numpy ``.npz`` of a flattened pytree + JSON manifest.

No orbax/flax in the container; this is deliberately simple but
crash-safe — the contract a long-running :class:`~repro.launch.service.
FederatedService` leans on:

* **Step dirs are atomic.**  Arrays and manifest are staged into a
  ``tmp*`` scratch dir and ``os.rename``'d into ``step_XXXXXXXX`` in one
  syscall, so a step directory is either absent or complete — a crash
  mid-save can never leave a half-written checkpoint behind.
* **The ``LATEST`` pointer is atomic and advisory.**  It is written via
  temp-file + ``os.replace``; :func:`latest_step` treats a missing,
  truncated, corrupt, or stale pointer as a cache miss and falls back to
  scanning the ``step_*`` dirs, so a torn pointer degrades to a
  directory listing rather than a crashed restore.
* **Crashed saves are garbage-collected.**  The next :func:`save` sweeps
  orphaned ``tmp*`` staging entries (single-writer discipline: one
  process saves into a given ``ckpt_dir`` at a time).
* **Retention.**  ``save(..., keep=K)`` prunes all but the newest K step
  dirs after the new one lands.
* **Restores are structure-checked.**  :func:`restore` validates the
  manifest's leaf *paths* against the target tree's paths — a target
  with a coinciding leaf count and shapes but different structure raises
  a diff-listing ``ValueError`` instead of silently loading leaves into
  the wrong slots.
* :func:`restore_tree` rebuilds the saved (string-dict-keyed) tree with
  no target template and returns the JSON ``meta`` recorded at save
  time — what a restarted service uses before it knows any shapes.
"""
from __future__ import annotations

import json
import os
import shutil
import tempfile

import jax
import numpy as np

_STEP_PREFIX = "step_"
_TMP_PREFIX = "tmp"


def _flatten_with_paths(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    paths = ["/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                      for k in path) for path, _ in flat]
    leaves = [leaf for _, leaf in flat]
    return paths, leaves, treedef


def _step_name(step: int) -> str:
    return f"{_STEP_PREFIX}{step:08d}"


def gc_tmp(ckpt_dir: str) -> list[str]:
    """Remove orphaned ``tmp*`` staging entries left by crashed saves
    (files and dirs; ``save`` calls this before staging its own).
    Returns the removed names."""
    removed = []
    try:
        entries = os.listdir(ckpt_dir)
    except FileNotFoundError:
        return removed
    for name in entries:
        if not name.startswith(_TMP_PREFIX):
            continue
        path = os.path.join(ckpt_dir, name)
        if os.path.isdir(path):
            shutil.rmtree(path, ignore_errors=True)
        else:
            try:
                os.remove(path)
            except OSError:
                continue
        removed.append(name)
    return removed


def steps(ckpt_dir: str) -> list[int]:
    """Sorted step numbers of the complete ``step_*`` dirs on disk (the
    rename-into-place protocol guarantees a listed dir is complete)."""
    try:
        entries = os.listdir(ckpt_dir)
    except FileNotFoundError:
        return []
    out = []
    for name in entries:
        if not name.startswith(_STEP_PREFIX):
            continue
        if not os.path.isdir(os.path.join(ckpt_dir, name)):
            continue
        try:
            out.append(int(name[len(_STEP_PREFIX):]))
        except ValueError:
            continue
    return sorted(out)


def _write_latest(ckpt_dir: str, name: str):
    fd, tmp = tempfile.mkstemp(dir=ckpt_dir, prefix=_TMP_PREFIX)
    try:
        with os.fdopen(fd, "w") as f:
            f.write(name)
        os.replace(tmp, os.path.join(ckpt_dir, "LATEST"))
    except BaseException:
        try:
            os.remove(tmp)
        except OSError:
            pass
        raise


def save(ckpt_dir: str, step: int, tree, *, meta: dict | None = None,
         keep: int | None = None) -> str:
    """Write one checkpoint.  ``meta`` is an arbitrary JSON-serializable
    dict stored in the manifest (round counters, accountant ledgers —
    anything that is not an array leaf).  ``keep`` retains only the
    newest ``keep`` step dirs after this one lands."""
    if keep is not None and keep < 1:
        raise ValueError(f"keep must retain at least the checkpoint "
                         f"being written, got keep={keep}")
    paths, leaves, _ = _flatten_with_paths(tree)
    os.makedirs(ckpt_dir, exist_ok=True)
    gc_tmp(ckpt_dir)
    target = os.path.join(ckpt_dir, _step_name(step))
    tmp = tempfile.mkdtemp(prefix=_TMP_PREFIX, dir=ckpt_dir)
    try:
        with jax.profiler.TraceAnnotation("checkpoint.d2h") as span:
            arrays = {f"a{i}": np.asarray(leaf)
                      for i, leaf in enumerate(leaves)}
            span.set_metadata(bytes=sum(a.nbytes for a in arrays.values()))
        np.savez(os.path.join(tmp, "arrays.npz"), **arrays)
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump({"step": step, "paths": paths, "meta": meta or {}}, f)
        if os.path.isdir(target):
            shutil.rmtree(target)
        os.rename(tmp, target)
    except BaseException:
        shutil.rmtree(tmp, ignore_errors=True)
        raise
    _write_latest(ckpt_dir, os.path.basename(target))
    if keep is not None:
        for old in steps(ckpt_dir)[:-keep]:
            if old != step:
                shutil.rmtree(os.path.join(ckpt_dir, _step_name(old)),
                              ignore_errors=True)
    return target


def latest_step(ckpt_dir: str) -> int | None:
    """Newest step on disk.  The ``LATEST`` pointer is consulted first;
    a missing/corrupt/stale pointer falls back to scanning the
    ``step_*`` dirs (None only when neither yields a step)."""
    try:
        with open(os.path.join(ckpt_dir, "LATEST")) as f:
            step = int(f.read().strip().split("_")[-1])
        if os.path.isdir(os.path.join(ckpt_dir, _step_name(step))):
            return step
    except (FileNotFoundError, ValueError):
        pass
    found = steps(ckpt_dir)
    return found[-1] if found else None


def _resolve_step(ckpt_dir: str, step: int | None) -> str:
    step = step if step is not None else latest_step(ckpt_dir)
    if step is None:
        raise FileNotFoundError(f"no checkpoint in {ckpt_dir}")
    d = os.path.join(ckpt_dir, _step_name(step))
    if not os.path.isdir(d):
        raise FileNotFoundError(f"no checkpoint dir {d}")
    return d


def _load_manifest(step_dir: str) -> dict:
    path = os.path.join(step_dir, "manifest.json")
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise FileNotFoundError(f"checkpoint {step_dir} has no "
                                "manifest.json") from None


def load_meta(ckpt_dir: str, step: int | None = None) -> dict:
    """The JSON ``meta`` dict recorded by :func:`save` (empty if the
    save passed none)."""
    return _load_manifest(_resolve_step(ckpt_dir, step)).get("meta", {})


def restore(ckpt_dir: str, target_tree, step: int | None = None):
    """Restore into the structure of ``target_tree``.

    The saved manifest's leaf paths must equal the target tree's leaf
    paths exactly (same names, same order); shapes are checked per leaf.
    A structural mismatch raises a ``ValueError`` listing the differing
    paths — equal leaf counts with coinciding shapes can no longer
    restore leaves into the wrong slots silently.
    """
    d = _resolve_step(ckpt_dir, step)
    saved_paths = _load_manifest(d)["paths"]
    paths, leaves, treedef = _flatten_with_paths(target_tree)
    if saved_paths != paths:
        saved_set, target_set = set(saved_paths), set(paths)
        only_ckpt = sorted(saved_set - target_set)
        only_target = sorted(target_set - saved_set)
        detail = []
        if only_ckpt:
            detail.append(f"only in checkpoint: {only_ckpt}")
        if only_target:
            detail.append(f"only in target: {only_target}")
        if not detail:
            detail.append("same leaves, different order: "
                          f"{saved_paths} vs {paths}")
        raise ValueError(
            f"checkpoint tree structure does not match the restore "
            f"target ({len(saved_paths)} vs {len(paths)} leaves); "
            + "; ".join(detail))
    data = np.load(os.path.join(d, "arrays.npz"))
    out = []
    for i, (path, tgt) in enumerate(zip(paths, leaves)):
        arr = data[f"a{i}"]
        if hasattr(tgt, "shape") and tuple(tgt.shape) != tuple(arr.shape):
            raise ValueError(f"shape mismatch at {path!r}: target "
                             f"{tuple(tgt.shape)} vs checkpoint "
                             f"{tuple(arr.shape)}")
        out.append(jax.numpy.asarray(arr, dtype=getattr(tgt, "dtype", None)))
    return jax.tree_util.tree_unflatten(treedef, out)


def restore_tree(ckpt_dir: str, step: int | None = None
                 ) -> tuple[dict, dict]:
    """Template-free restore: rebuild the saved tree as nested dicts of
    numpy arrays straight from the manifest paths, plus the ``meta``
    dict.  Only trees whose containers are string-keyed dicts round-trip
    through this (a single bare array round-trips too); that is the
    service checkpoint layout by construction."""
    d = _resolve_step(ckpt_dir, step)
    manifest = _load_manifest(d)
    saved_paths = manifest["paths"]
    data = np.load(os.path.join(d, "arrays.npz"))
    arrays = [data[f"a{i}"] for i in range(len(saved_paths))]
    if saved_paths == [""]:  # the tree was one bare array
        return arrays[0], manifest.get("meta", {})
    tree: dict = {}
    for path, arr in zip(saved_paths, arrays):
        node = tree
        parts = path.split("/")
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = arr
    return tree, manifest.get("meta", {})
