"""Step-atomic checkpointing of (params, optimizer, data-plane cursor, RNG).

Layout: one directory per step —

    <dir>/step_000123/
        manifest.json    tree structure + dtypes + loader state + metadata
        arrays.npz       flat leaves keyed by tree path

Writes are ATOMIC (tmp dir + os.rename) so a preempted node never leaves a
half-written checkpoint, and ``latest_step`` only believes a directory that
contains a manifest.  ``save_async`` runs serialization on a worker thread —
the training loop donates a host copy and keeps stepping (compute/IO
overlap, the same trick DELI's pre-fetcher plays on the input side).

Restore is sharding-aware: pass ``like`` (ShapeDtypeStructs with shardings)
and leaves are placed with jax.device_put against each leaf's sharding.
"""
from __future__ import annotations

import json
import os
import pathlib
import re
import shutil
import tempfile
import threading
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np


def _flatten(tree) -> Dict[str, Any]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(
            str(getattr(p, "key", getattr(p, "idx", p))) for p in path
        )
        flat[key] = leaf
    return flat


def save_checkpoint(
    directory: str,
    step: int,
    params,
    opt_state,
    loader_state: Optional[dict] = None,
    rng: Optional[jax.Array] = None,
    extra: Optional[dict] = None,
) -> str:
    """Synchronous atomic save; returns the checkpoint path."""
    base = pathlib.Path(directory)
    base.mkdir(parents=True, exist_ok=True)
    final = base / f"step_{step:08d}"
    tmp = pathlib.Path(tempfile.mkdtemp(dir=base, prefix=".tmp_ckpt_"))
    try:
        tree = {"params": params, "opt": opt_state}
        if rng is not None:
            tree["rng"] = rng
        flat = _flatten(tree)
        arrays = {k: np.asarray(v) for k, v in flat.items()}
        np.savez(tmp / "arrays.npz", **arrays)
        manifest = {
            "step": step,
            "keys": sorted(arrays.keys()),
            "dtypes": {k: str(v.dtype) for k, v in arrays.items()},
            "loader_state": loader_state or {},
            "extra": extra or {},
        }
        (tmp / "manifest.json").write_text(json.dumps(manifest, indent=1))
        if final.exists():
            shutil.rmtree(final)
        os.rename(tmp, final)
    except BaseException:
        shutil.rmtree(tmp, ignore_errors=True)
        raise
    return str(final)


class AsyncCheckpointer:
    """Overlaps checkpoint serialization with training compute.

    ``save()`` snapshots the pytrees to host memory synchronously (cheap),
    then writes on a background thread; ``wait()`` joins before the next
    save or at shutdown so at most one write is in flight."""

    def __init__(self, directory: str, keep: int = 3):
        self.directory = directory
        self.keep = keep
        self._thread: Optional[threading.Thread] = None
        self.last_path: Optional[str] = None
        self.error: Optional[BaseException] = None

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self.error is not None:
            err, self.error = self.error, None
            raise err

    def save(self, step: int, params, opt_state, loader_state=None, rng=None, extra=None):
        self.wait()
        # A copy of its own, taken now: the next step may be handed the
        # device buffers (donated) and write over them.
        host = jax.tree.map(lambda x: np.array(x, copy=True), {"p": params, "o": opt_state})

        def work():
            try:
                self.last_path = save_checkpoint(
                    self.directory, step, host["p"], host["o"], loader_state, rng, extra
                )
                self._gc()
            except BaseException as e:  # surfaced on next wait()
                self.error = e

        self._thread = threading.Thread(target=work, daemon=True)
        self._thread.start()

    def _gc(self):
        steps = list_steps(self.directory)
        for s in steps[: -self.keep]:
            shutil.rmtree(
                pathlib.Path(self.directory) / f"step_{s:08d}", ignore_errors=True
            )


def list_steps(directory: str):
    base = pathlib.Path(directory)
    if not base.exists():
        return []
    out = []
    for d in base.iterdir():
        m = re.fullmatch(r"step_(\d+)", d.name)
        if m and (d / "manifest.json").exists():
            out.append(int(m.group(1)))
    return sorted(out)


def latest_step(directory: str) -> Optional[int]:
    steps = list_steps(directory)
    return steps[-1] if steps else None


def restore_checkpoint(
    directory: str,
    step: Optional[int] = None,
    like: Optional[Tuple] = None,
) -> Tuple[Any, Any, dict, dict]:
    """Returns (params, opt_state, loader_state, extra).

    ``like`` = (params_like, opt_like) pytrees of ShapeDtypeStruct (with
    shardings for distributed restore) or arrays; leaves are device_put
    against the target sharding when present."""
    step = step if step is not None else latest_step(directory)
    if step is None:
        raise FileNotFoundError(f"no checkpoints under {directory}")
    path = pathlib.Path(directory) / f"step_{step:08d}"
    manifest = json.loads((path / "manifest.json").read_text())
    dtypes = manifest["dtypes"]
    with np.load(path / "arrays.npz") as z:
        # np.savez writes ml_dtypes leaves (bfloat16) as raw void records
        # ('|V2'); the manifest's dtype name views the bytes back losslessly.
        arrays = {k: z[k].view(jax.numpy.dtype(dtypes[k])) for k in z.files}

    def unflatten(like_tree, prefix):
        flat_paths = jax.tree_util.tree_flatten_with_path(like_tree)
        leaves = []
        for p, leaf in flat_paths[0]:
            key = prefix + "/" + "/".join(
                str(getattr(q, "key", getattr(q, "idx", q))) for q in p
            )
            arr = arrays[key]
            sharding = getattr(leaf, "sharding", None)
            if sharding is not None:
                leaves.append(jax.device_put(arr.astype(leaf.dtype), sharding))
            else:
                leaves.append(jax.numpy.asarray(arr))
        return jax.tree_util.tree_unflatten(flat_paths[1], leaves)

    if like is not None:
        params = unflatten(like[0], "params")
        opt = unflatten(like[1], "opt")
    else:
        params = {
            k[len("params/"):]: v for k, v in arrays.items() if k.startswith("params/")
        }
        opt = {k[len("opt/"):]: v for k, v in arrays.items() if k.startswith("opt/")}
    return params, opt, manifest.get("loader_state", {}), manifest.get("extra", {})
