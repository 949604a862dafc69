"""Fault-tolerant checkpointing.

Design (per DESIGN.md §6):
  * mesh-agnostic: leaves are gathered to host and stored dense, so a job
    restarted on a DIFFERENT mesh (elastic re-scale, pod loss) re-shards on
    load via the new mesh's shardings;
  * atomic: write to step_N.tmp/, fsync EVERY file (arrays and meta.json)
    plus the directories, os.replace -> step_N/ — a crash at any point,
    including right after the rename, never persists a checkpoint whose
    arrays did not hit disk;
  * integrity: per-array crc32 stored in meta.json and verified on restore;
    a corrupt checkpoint is skipped and the previous one restored;
  * keep-last-k pruning + optional async (background thread) saves, with a
    manager-wide lock so an async save/prune can never race a concurrent
    restore reading a step directory mid-delete.
"""
from __future__ import annotations

import json
import os
import shutil
import threading
import zlib

import jax
import numpy as np

_SEP = "/"


def _flatten(tree):
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = {}
    for path, leaf in flat:
        key = _SEP.join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        out[key] = leaf
    return out


class CheckpointManager:
    def __init__(self, directory: str, keep: int = 3):
        self.dir = directory
        self.keep = keep
        os.makedirs(directory, exist_ok=True)
        self._thread = None
        # serializes write/prune against restore reads (RLock: _write
        # calls _prune while holding it) — an async save can otherwise
        # delete a step directory out from under a concurrent restore
        self._lock = threading.RLock()

    # ------------------------------------------------------------- save
    def save(self, step: int, state, blocking: bool = True,
             extra_meta: dict | None = None):
        """Gather to host and persist. With blocking=False the serialization
        happens on a background thread (training continues). ``extra_meta``:
        JSON-able dict stored under meta.json["extra"] — static context a
        restoring job needs before it can build a template (e.g. a
        MultiSketchSpec encoding, see core.multi_sketch.spec_to_meta)."""
        self.wait()  # never two writers at once (same-step races included)
        if step in self.list_steps():
            return
        host = {k: np.asarray(jax.device_get(v))
                for k, v in _flatten(state).items()}
        if blocking:
            self._write(step, host, extra_meta)
        else:
            self._thread = threading.Thread(
                target=self._write, args=(step, host, extra_meta),
                daemon=True)
            self._thread.start()

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    @staticmethod
    def _fsync_dir(path: str):
        fd = os.open(path, os.O_RDONLY)
        try:
            os.fsync(fd)
        finally:
            os.close(fd)

    def _write(self, step: int, host: dict, extra_meta: dict | None = None):
        with self._lock:
            final = os.path.join(self.dir, f"step_{step:010d}")
            tmp = final + ".tmp"
            if os.path.exists(tmp):
                shutil.rmtree(tmp)
            os.makedirs(tmp)
            meta = {"step": step, "arrays": {}, "extra": extra_meta or {}}
            for k, v in host.items():
                fn = k.replace(_SEP, "__") + ".npy"
                # fsync each array file: the rename below only orders the
                # DIRECTORY entry — without these fsyncs a crash after
                # os.replace can persist a checkpoint whose array bytes
                # never hit disk (meta.json alone was never enough)
                with open(os.path.join(tmp, fn), "wb") as f:
                    np.save(f, v)
                    f.flush()
                    os.fsync(f.fileno())
                meta["arrays"][k] = {
                    "file": fn, "crc": zlib.crc32(v.tobytes()) & 0xFFFFFFFF,
                    "shape": list(v.shape), "dtype": str(v.dtype)}
            with open(os.path.join(tmp, "meta.json"), "w") as f:
                json.dump(meta, f)
                f.flush()
                os.fsync(f.fileno())
            self._fsync_dir(tmp)       # file entries durable before rename
            if os.path.exists(final):
                shutil.rmtree(final)
            os.replace(tmp, final)
            self._fsync_dir(self.dir)  # the rename itself durable
            self._prune()

    def _prune(self):
        with self._lock:
            steps = self.list_steps()
            for s in steps[:-self.keep]:
                shutil.rmtree(os.path.join(self.dir, f"step_{s:010d}"),
                              ignore_errors=True)

    # ---------------------------------------------------------- restore
    def list_steps(self):
        out = []
        for d in os.listdir(self.dir):
            if d.startswith("step_") and not d.endswith(".tmp"):
                try:
                    out.append(int(d[5:]))
                except ValueError:
                    pass
        return sorted(out)

    def read_meta(self, step: int | None = None):
        """(step, meta dict) of the given — else the newest readable —
        checkpoint, without loading arrays. The restore entry point for
        jobs that must reconstruct their state TEMPLATE from the stored
        ``extra`` metadata first (e.g. SegmentQueryEngine.from_checkpoint).
        Raises FileNotFoundError when no checkpoint is readable."""
        steps = [step] if step is not None else reversed(self.list_steps())
        for s in steps:
            try:
                with self._lock, \
                        open(os.path.join(self.dir, f"step_{s:010d}",
                                          "meta.json")) as f:
                    return s, json.load(f)
            except (OSError, ValueError):   # missing OR corrupt json
                continue
        raise FileNotFoundError(f"no readable checkpoint under {self.dir}")

    def _load(self, step: int):
        with self._lock:   # a concurrent save's prune must not delete the
            d = os.path.join(self.dir, f"step_{step:010d}")  # dir mid-read
            with open(os.path.join(d, "meta.json")) as f:
                meta = json.load(f)
            arrays = {}
            for k, info in meta["arrays"].items():
                v = np.load(os.path.join(d, info["file"]))
                if (zlib.crc32(v.tobytes()) & 0xFFFFFFFF) != info["crc"]:
                    raise IOError(f"checksum mismatch for {k} at step {step}")
                arrays[k] = v
            return meta["step"], arrays

    def restore_step(self, step: int, template, shardings=None):
        """Restore ONE specific step into ``template``'s structure, or None
        if that step is corrupt/partial. Lets a caller that derives the
        template from the step's own metadata (read_meta) keep meta and
        arrays from the SAME checkpoint while falling back step by step."""
        try:
            step, arrays = self._load(step)
        except Exception as e:  # corrupt -> caller tries previous
            print(f"[ckpt] skipping step {step}: {e}")
            return None
        keys = _flatten(template)
        missing = set(keys) - set(arrays)
        if missing:
            print(f"[ckpt] step {step} missing {len(missing)} arrays")
            return None
        shard_map_ = _flatten(shardings) if shardings is not None else {}
        flat, treedef = jax.tree_util.tree_flatten(template)
        vals = []
        for k, tpl in keys.items():
            arr = arrays[k]
            sh = shard_map_.get(k)
            if sh is not None:
                vals.append(jax.device_put(arr, sh))
            else:
                vals.append(jax.device_put(arr))
        return jax.tree_util.tree_unflatten(treedef, vals)

    def restore_latest(self, template, shardings=None):
        """Restore the newest intact checkpoint into ``template``'s structure.
        Corrupt/partial checkpoints are skipped (fault tolerance). Returns
        (state, step) or (None, -1)."""
        with self._lock:   # a concurrent save's prune must not delete every
            for step in reversed(self.list_steps()):   # listed step first
                state = self.restore_step(step, template, shardings)
                if state is not None:
                    return state, step
        return None, -1
