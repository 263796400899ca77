"""save_state_dict / load_state_dict implementation.

Checkpoint layout on disk:
    <path>/
      metadata.json             # {tensors: {name: {shape, dtype, shards: [...]}}}
      <rank>_<n>.npy            # one .npy per locally-written unique shard

Each shard record: {"offset": [d0, d1, ...], "shape": [...], "file": "..."}.
Offsets are global start indices of the shard block.  Duplicate shards
(replicated placements) are written once by the lowest-id owning device.
"""
from __future__ import annotations

import json
import os
import re

import numpy as np
import jax

__all__ = ["save_state_dict", "load_state_dict",
           "clear_async_save_task_queue"]


def _flatten(state_dict, prefix=""):
    flat = {}
    for k, v in state_dict.items():
        name = f"{prefix}.{k}" if prefix else str(k)
        if isinstance(v, dict):
            flat.update(_flatten(v, name))
        else:
            flat[name] = v
    return flat


def _to_jax_array(v):
    from ...core.tensor import Tensor
    if isinstance(v, Tensor):
        return v._data
    if isinstance(v, jax.Array):
        return v
    import jax.numpy as jnp
    return jnp.asarray(np.asarray(v))


def _safe(name: str) -> str:
    return re.sub(r"[^A-Za-z0-9_.-]", "_", name)


def _process_rank() -> int:
    return getattr(jax, "process_index", lambda: 0)()


def _existing_uids(path):
    import glob
    uids = set()
    for fp in glob.glob(os.path.join(path, "metadata_*.json")):
        m = re.match(r"metadata_(\d+)\.\d+\.json$", os.path.basename(fp))
        if m:
            uids.add(int(m.group(1)))
    return uids


def _offset_of(idx):
    return tuple((s.start or 0) if isinstance(s, slice) else int(s)
                 for s in idx)


def save_state_dict(state_dict, path, process_group=None,
                    coordinator_rank=0, unique_id=None, keep=2,
                    async_save=False):
    """Write every rank's local shards + a global metadata file.

    state_dict: (nested) dict of Tensor / jax.Array / numpy.  Works for
    replicated, sharded, and hybrid (mesh) placements alike.

    Checkpoint files are versioned by `unique_id`; ranks of one logical
    save never delete each other's in-flight files (the round-1 cleanup
    race), because load reads only the newest complete version and the
    coordinator prunes only versions older than the newest `keep`.
    Single-process saves may omit unique_id (auto: max existing + 1);
    multi-process saves MUST pass a shared unique_id (e.g. the step
    number) because directory scans on skewed ranks can disagree — the
    reference solves the same problem by all_gather'ing the id
    (reference python/paddle/distributed/checkpoint/save_state_dict.py).

    async_save=True (reference save_state_dict async_save): device->host
    copies happen synchronously (training may mutate the arrays right
    after this returns), then file writes run on a background task —
    wait with clear_async_save_task_queue().
    """
    os.makedirs(path, exist_ok=True)
    rank = _process_rank()
    if unique_id is None:
        if getattr(jax, "process_count", lambda: 1)() > 1:
            raise ValueError(
                "save_state_dict: multi-process saves must pass a shared "
                "unique_id (e.g. the global step) — auto-assignment by "
                "directory scan races across skewed ranks")
        # in-flight async saves haven't written metadata yet: their uids
        # must count too or back-to-back async saves collide on files.
        # Read them BEFORE the directory: a writer drops its uid only
        # after its metadata is there, so one that finishes in between
        # is seen by the scan (the other order misses it in both)
        uids = set(_issued_uids.get(os.path.abspath(path), set()))
        uids |= _existing_uids(path)
        unique_id = (max(uids) + 1) if uids else 0
    _issued_uids.setdefault(os.path.abspath(path), set()).add(unique_id)
    flat = _flatten(state_dict)
    meta = {"tensors": {}}
    n_files = 0
    pending_writes = []
    for name, val in flat.items():
        arr = _to_jax_array(val)
        shards_meta = []
        # Replicated blocks are written once GLOBALLY: only the process
        # owning the lowest-id device that holds a given offset block writes
        # it (the reference's dedup_tensor step).
        owner = {}
        try:
            for dev, idx in arr.sharding.devices_indices_map(
                    arr.shape).items():
                off = _offset_of(idx) if idx else ()
                if off not in owner or dev.id < owner[off].id:
                    owner[off] = dev
        except Exception:
            owner = None  # single-device / odd sharding: local dedup below
        seen_offsets = set()
        addressable = {sh.device for sh in arr.addressable_shards}
        for sh in arr.addressable_shards:
            offset = _offset_of(sh.index) if sh.index else ()
            if offset in seen_offsets:
                continue  # replicated copy within this process: write once
            if owner is not None and owner.get(offset) is not None \
                    and owner[offset] not in addressable:
                continue  # a lower-id device on another process owns it
            seen_offsets.add(offset)
            local = np.asarray(sh.data)
            if local.dtype.name == "bfloat16":
                # .npy has no bf16: store the raw bits as uint16 (the
                # recorded tensor dtype restores the view on load)
                local = local.view(np.uint16)
            fname = f"{unique_id}.{rank}_{n_files}.npy"
            if async_save:
                # force a real host copy: on the CPU backend np.asarray can
                # alias the device buffer, which a donated train step would
                # overwrite mid-write
                pending_writes.append((fname, np.array(local, copy=True)))
            else:
                # sync path streams each shard straight to disk (buffering
                # the whole checkpoint would double peak host memory)
                np.save(os.path.join(path, fname), local)
            n_files += 1
            shards_meta.append({
                "offset": list(offset),
                "shape": list(local.shape),
                "file": fname,
            })
        meta["tensors"][name] = {
            "shape": list(arr.shape),
            "dtype": str(arr.dtype),
            "shards": shards_meta,
        }
    def _write():
        for fname, local in pending_writes:
            np.save(os.path.join(path, fname), local)
        # metadata LAST: its presence marks the version complete for load
        # (each rank writes its OWN file — no write races; load merges)
        tmp = os.path.join(path, f".metadata_{unique_id}.{rank}.json.tmp")
        with open(tmp, "w") as f:
            json.dump(meta, f)
        os.replace(tmp,
                   os.path.join(path, f"metadata_{unique_id}.{rank}.json"))
        # only now does a directory scan see this uid: dropped before the
        # metadata was there, a save that scanned in between took it again
        _issued_uids.get(os.path.abspath(path), set()).discard(unique_id)
        if rank == coordinator_rank and keep is not None:
            _prune_old_versions(path, unique_id, keep)

    if async_save:
        import threading

        box = {"error": None}

        def _guarded():
            try:
                _write()
            except BaseException as e:   # surfaced by clear_...
                box["error"] = e
            finally:
                # in-flight set holds only unwritten uids
                _issued_uids.get(os.path.abspath(path),
                                 set()).discard(unique_id)

        t = threading.Thread(target=_guarded, daemon=True,
                             name=f"ckpt-save-{unique_id}")
        t._error_box = box
        t.start()
        _async_save_queue.append(t)
        return unique_id
    _write()
    return unique_id


_async_save_queue = []
_issued_uids: dict = {}


def clear_async_save_task_queue(timeout=60.0):
    """Wait until every in-flight async save finishes; a failed background
    write re-raises HERE (reference clear_async_save_task_queue + its
    exitcode check) so a broken checkpoint can never pass silently."""
    while _async_save_queue:
        t = _async_save_queue.pop()
        if t.is_alive():
            t.join(timeout=timeout)
            if t.is_alive():
                _async_save_queue.append(t)
                raise TimeoutError(
                    f"async checkpoint save {t.name} still running after "
                    f"{timeout}s")
        err = getattr(t, "_error_box", {}).get("error")
        if err is not None:
            raise RuntimeError(
                f"async checkpoint save {t.name} failed") from err


def _prune_old_versions(path, current_uid, keep):
    """Delete files of versions older than the newest `keep` — safe at any
    time because peers only ever write the CURRENT uid and load reads only
    the max uid."""
    import glob
    uids = sorted(u for u in _existing_uids(path) | {current_uid})
    for old in uids[:-keep] if keep > 0 else uids:
        if old == current_uid:
            continue
        for f in (glob.glob(os.path.join(path, f"metadata_{old}.*.json"))
                  + glob.glob(os.path.join(path, f"{old}.*.npy"))):
            try:
                os.remove(f)
            except OSError:
                pass


def _read_meta(path):
    """Merge the newest version's metadata files into one tensor->shards map.

    Falls back to legacy (unversioned `metadata.json` / `metadata.<r>.json`)
    checkpoints when no versioned files exist.
    """
    import glob
    uids = _existing_uids(path)
    if uids:
        files = sorted(
            glob.glob(os.path.join(path, f"metadata_{max(uids)}.*.json")))
    else:
        files = sorted(glob.glob(os.path.join(path, "metadata*.json")))
    if not files:
        raise FileNotFoundError(f"no metadata files under {path}")
    tensors = {}
    for fp in files:
        with open(fp) as f:
            part = json.load(f)
        for name, tmeta in part["tensors"].items():
            if name in tensors:
                tensors[name]["shards"].extend(tmeta["shards"])
            else:
                tensors[name] = tmeta
    return tensors


def _load_npy(path, fname, dtype_name):
    # mmap: partial-block reshard reads touch only the needed slices
    data = np.load(os.path.join(path, fname), mmap_mode="r")
    if dtype_name == "bfloat16":
        import ml_dtypes
        data = data.view(ml_dtypes.bfloat16)
    return data


def _read_block(path, tmeta, want_offset, want_shape):
    """Assemble the [want_offset, want_offset+want_shape) block of a tensor
    from whatever saved shards overlap it."""
    dtype_name = tmeta["dtype"]
    if dtype_name == "bfloat16":
        import ml_dtypes
        out = np.empty(want_shape, dtype=ml_dtypes.bfloat16)
    else:
        out = np.empty(want_shape, dtype=np.dtype(dtype_name))
    filled = np.zeros(want_shape, dtype=bool) if out.size else None
    ndim = len(want_shape)
    if ndim == 0:
        return _load_npy(path, tmeta["shards"][0]["file"], dtype_name)
    for sh in tmeta["shards"]:
        s_off, s_shape = sh["offset"], sh["shape"]
        # overlap of [s_off, s_off+s_shape) with [want_offset, +want_shape)
        lo = [max(s_off[d], want_offset[d]) for d in range(ndim)]
        hi = [min(s_off[d] + s_shape[d], want_offset[d] + want_shape[d])
              for d in range(ndim)]
        if any(l >= h for l, h in zip(lo, hi)):
            continue
        data = _load_npy(path, sh["file"], dtype_name)
        src = tuple(slice(lo[d] - s_off[d], hi[d] - s_off[d])
                    for d in range(ndim))
        dst = tuple(slice(lo[d] - want_offset[d], hi[d] - want_offset[d])
                    for d in range(ndim))
        out[dst] = data[src]
        if filled is not None:
            filled[dst] = True
    if filled is not None and not filled.all():
        raise ValueError("checkpoint is missing data for requested block "
                         f"(offset={want_offset}, shape={want_shape})")
    return out


def _load_one(path, tmeta, target):
    """Produce a jax.Array matching `target`'s sharding, filled from disk."""
    import jax.numpy as jnp
    global_shape = tuple(tmeta["shape"])
    sharding = target.sharding
    dtype = target.dtype
    if tuple(target.shape) != global_shape:
        raise ValueError(
            f"shape mismatch: checkpoint {global_shape} vs target "
            f"{tuple(target.shape)}")
    if not getattr(target, "committed", True):
        # uncommitted target: plain array, free to migrate between devices
        full = _read_block(path, tmeta, (0,) * len(global_shape),
                           global_shape)
        return jnp.asarray(full).astype(dtype)
    idx_map = sharding.addressable_devices_indices_map(global_shape)
    per_device = []
    block_cache = {}  # replicated layouts share one disk read per block
    for dev, idx in idx_map.items():
        offset = tuple((s.start or 0) for s in idx) if idx else ()
        shape = tuple(
            ((s.stop if s.stop is not None else global_shape[d]) -
             (s.start or 0))
            for d, s in enumerate(idx)) if idx else ()
        key = (offset, shape)
        block = block_cache.get(key)
        if block is None:
            block = block_cache[key] = jnp.asarray(
                _read_block(path, tmeta, offset, shape)).astype(dtype)
        per_device.append(jax.device_put(block, dev))
    return jax.make_array_from_single_device_arrays(
        global_shape, sharding, per_device)


def load_state_dict(state_dict, path, process_group=None,
                    coordinator_rank=0, unique_id=None, offload=False):
    """Fill `state_dict`'s tensors in place from a checkpoint at `path`,
    resharding to each target's CURRENT sharding/placement (which may differ
    from the one it was saved with)."""
    from ...core.tensor import Tensor
    tensors = _read_meta(path)

    def walk(d, prefix=""):
        for k, v in d.items():
            name = f"{prefix}.{k}" if prefix else str(k)
            if isinstance(v, dict):
                walk(v, name)
                continue
            if name not in tensors:
                raise KeyError(f"'{name}' not found in checkpoint {path}")
            tmeta = tensors[name]
            if isinstance(v, Tensor):
                v._data = _load_one(path, tmeta, v._data)
            elif isinstance(v, jax.Array):
                d[k] = _load_one(path, tmeta, v)
            else:
                block = _read_block(path, tmeta,
                                    (0,) * len(tmeta["shape"]),
                                    tuple(tmeta["shape"]))
                d[k] = block
    walk(state_dict)
    return state_dict
