"""AssiseCheckpointer: training state through the CC-NVM layer.

Each worker owns a LibState (colocated persistent cache + chain
replication). A checkpoint is a set of per-leaf PUTs — the operation
granularity the paper advocates; each leaf is copied to the host whole,
so a sharded leaf is gathered — followed by a manifest PUT
and an fsync (pessimistic: survives the worker AND its node) or dsync
(optimistic: coalesced; bounded at-risk window). In full mode prefix
semantics make the manifest write the atomic commit point: a restore
only ever sees a fully-written checkpoint.

Delta mode logs only changed blocks vs. the previous step (redundant-
write elimination for sparse-update tensors: embeddings, cold experts).
Each leaf lives at a **stable key** and a step's changes are emitted as
``LibState.write`` byte-range writes straight from the changed-block
bitmap (indices × block → offsets). On a TPU the compiled Pallas
``delta_mask`` kernel scans each leaf's tile-aligned prefix; the host
scan covers the tail, and the whole leaf on any other platform. A
kernel error propagates: there is no silent fallback. Only the changed ranges
are logged, replicated, and digested; the tradeoff vs per-step blobs is
that in-place deltas make only the *latest* step restorable (older
manifests are kept solely as the commit-point protocol's history), and
a crash mid-save can leave a newer step's partial patches on the stable
keys — manifests carry per-leaf CRCs so ``restore`` detects that and
returns None instead of silently corrupt tensors.

Restore order (the paper's failover story): process-local log ->
node-local hot area -> chain replica NVM -> cold storage — sub-second
for everything above cold.
"""
from __future__ import annotations

import io
import json
import sys
import threading
import time
import zlib
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from repro.ckpt.delta import changed_blocks, changed_extents
from repro.core.store import LibState

_KERNEL_BPT = 8  # blocks per delta_mask grid step


def kernel_changed_blocks(new: bytes, old: bytes, block: int, *,
                          interpret: bool) -> Tuple[List[int], int]:
    """Changed blocks of the tile-aligned prefix of two equal-length
    encodings, found by the Pallas ``delta_mask`` kernel. The bytes go
    to the device as 32-bit words viewed on the host (the kernel's
    interface). Returns (block indices, prefix bytes scanned)."""
    import jax.numpy as jnp

    from repro.kernels.delta_encode import delta_mask
    tile = block * _KERNEL_BPT
    aligned = len(new) // tile * tile
    if not aligned:
        return [], 0
    nw = np.frombuffer(new, np.uint32, count=aligned // 4)
    ow = np.frombuffer(old, np.uint32, count=aligned // 4)
    mask = delta_mask(jnp.asarray(nw), jnp.asarray(ow), block=block,
                      bpt=_KERNEL_BPT, interpret=interpret)
    return np.flatnonzero(np.asarray(mask)).tolist(), aligned


def changed_block_idxs(new: bytes, old: bytes, block: int, scan=None
                       ) -> Tuple[List[int], int]:
    """Changed-block indices of ``new`` against ``old``: ``scan`` (the
    kernel) on the tile-aligned prefix when given, the host scan on the
    rest. Returns (indices, bytes the kernel scanned)."""
    idxs, aligned = scan(new, old, block) if scan is not None else ([], 0)
    tail = changed_blocks(new[aligned:], old[aligned:], block)
    return idxs + [i + aligned // block for i in tail], aligned


def _device_scan():
    """The scan this process runs on each leaf's tile-aligned prefix:
    the compiled kernel on a TPU, None (host scan only) elsewhere. A TPU
    training process has JAX loaded already; without JAX there is no
    device to scan on, so the import is not paid just to ask."""
    jax = sys.modules.get("jax")
    if jax is None or jax.default_backend() != "tpu":
        return None
    return partial(kernel_changed_blocks, interpret=False)


@dataclass(frozen=True)
class CheckpointConfig:
    prefix: str = "/ckpt/run0"
    mode: str = "pessimistic"  # fsync vs dsync on commit
    delta: bool = True
    delta_block: int = 1 << 16
    keep: int = 2  # checkpoints retained before delete
    async_commit: bool = False  # overlap replication with next step


def _encode_leaf(arr: np.ndarray) -> bytes:
    bio = io.BytesIO()
    np.save(bio, arr, allow_pickle=False)
    return bio.getvalue()


def _decode_leaf(data: bytes) -> np.ndarray:
    return np.load(io.BytesIO(data), allow_pickle=False)


def _flatten(tree: Any, prefix: str = "") -> Dict[str, np.ndarray]:
    out = {}
    if isinstance(tree, dict):
        for k, v in tree.items():
            out.update(_flatten(v, f"{prefix}/{k}"))
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            out.update(_flatten(v, f"{prefix}/{i}"))
    else:
        out[prefix] = tree
    return out


class AssiseCheckpointer:
    def __init__(self, store: LibState, cfg: CheckpointConfig =
                 CheckpointConfig()):
        self.store = store
        self.cfg = cfg
        self._prev: Dict[str, bytes] = {}  # previous encoded leaves
        self._saved_steps = []
        self._pending: Optional[threading.Thread] = None
        # changed-block scan of each leaf's tile-aligned prefix, chosen
        # once by platform; the rest of every leaf is host-scanned
        self._scan = _device_scan()
        # *_s: seconds per save phase, summed over saves (d2h, encode
        # and scan from their spans); kernel_bytes / host_scan_bytes:
        # bytes whose changed blocks each scan found
        self.stats = {"bytes_full": 0, "bytes_logged": 0, "saves": 0,
                      "d2h_s": 0.0, "encode_s": 0.0,
                      "scan_s": 0.0, "put_s": 0.0, "fsync_s": 0.0,
                      "kernel_bytes": 0, "host_scan_bytes": 0}

    @property
    def last_saved(self) -> Dict[str, bytes]:
        """Encoded leaves of the last save: what a restore of that step
        must reproduce byte for byte."""
        return self._prev

    def _leaf_key(self, step: int, name: str) -> str:
        if self.cfg.delta:  # stable key: steps patch it in place
            return f"{self.cfg.prefix}/data{name}"
        return f"{self.cfg.prefix}/data/{step}{name}"

    # -- save ----------------------------------------------------------------
    def save(self, step: int, state: Any, extra: Optional[dict] = None):
        """Write one checkpoint. state: pytree of arrays (numpy/JAX)."""
        self.wait()  # serialize with any pending async commit
        leaves = _flatten(state)
        with self.store.tracer.span("ckpt.save", step=step,
                                    leaves=len(leaves)):
            self._save(step, leaves, extra)

    def _save(self, step: int, leaves: Dict[str, Any],
              extra: Optional[dict]):
        span = self.store.tracer.span
        manifest = {"step": step, "leaves": sorted(leaves),
                    "extra": extra or {},
                    "format": "range" if self.cfg.delta else "full",
                    "leaf_crc": {}}
        new_prev = {}
        st = self.stats
        for name, arr in leaves.items():
            with span("ckpt.d2h") as sp:
                host = np.asarray(arr)  # device leaves copy out one by one
                sp.count(nbytes=host.nbytes)
            st["d2h_s"] += sp.seconds
            with span("ckpt.encode", nbytes=host.nbytes) as sp:
                raw = _encode_leaf(host)
                del host
                manifest["leaf_crc"][name] = zlib.crc32(raw) & 0xFFFFFFFF
            st["encode_s"] += sp.seconds
            st["bytes_full"] += len(raw)
            key = self._leaf_key(step, name)
            old = self._prev.get(name) if self.cfg.delta else None
            extents = None
            if old is not None and len(old) == len(raw):
                with span("ckpt.scan") as sp:
                    idxs, aligned = changed_block_idxs(
                        raw, old, self.cfg.delta_block, self._scan)
                    extents = changed_extents(raw, old, self.cfg.delta_block,
                                              idxs=idxs)
                    sp.count(kernel_bytes=aligned,
                             host_bytes=len(raw) - aligned)
                st["scan_s"] += sp.seconds
                st["kernel_bytes"] += aligned
                st["host_scan_bytes"] += len(raw) - aligned
            t = time.perf_counter()
            if extents is not None and \
                    sum(ln for _, ln in extents) < len(raw):
                for off, ln in extents:  # range writes: the paper's
                    # op-granularity — only changed bytes hit the log
                    self.store.write(key, raw[off:off + ln], off)
                    st["bytes_logged"] += ln
            else:
                self.store.put(key, raw)
                st["bytes_logged"] += len(raw)
            st["put_s"] += time.perf_counter() - t
            new_prev[name] = raw
        # manifest last: the atomic commit point under prefix semantics
        self.store.put(f"{self.cfg.prefix}/MANIFEST.{step}",
                       json.dumps(manifest).encode())
        self.store.put(f"{self.cfg.prefix}/LATEST",
                       str(step).encode())

        def commit():
            t = time.perf_counter()
            if self.cfg.mode == "pessimistic":
                self.store.fsync()
            else:
                self.store.dsync()
            st["fsync_s"] += time.perf_counter() - t

        if self.cfg.async_commit:
            self._pending = threading.Thread(target=commit)
            self._pending.start()
        else:
            commit()
        self._prev = new_prev
        self._saved_steps.append(step)
        self.stats["saves"] += 1
        self._gc()

    def wait(self):
        if self._pending is not None:
            self._pending.join()
            self._pending = None

    def _gc(self):
        while len(self._saved_steps) > self.cfg.keep:
            old = self._saved_steps.pop(0)
            man = self.store.get(f"{self.cfg.prefix}/MANIFEST.{old}")
            if man is None:
                continue
            m = json.loads(man)
            if m.get("format") != "range":
                # per-step leaves are private to this checkpoint
                for name in m["leaves"]:
                    self.store.delete(f"{self.cfg.prefix}/data/{old}{name}")
            # range mode: leaves live at stable keys shared by every step
            self.store.delete(f"{self.cfg.prefix}/MANIFEST.{old}")

    # -- restore ------------------------------------------------------------
    def latest_step(self) -> Optional[int]:
        v = self.store.get(f"{self.cfg.prefix}/LATEST")
        return int(v) if v is not None else None

    def restore(self, step: Optional[int] = None):
        """Returns (state_dict {name: np.ndarray}, manifest) or None.

        Range-format checkpoints patch stable keys in place, so only
        the step the manifests agree is latest can be reassembled;
        asking for an older range-format step returns None."""
        self.wait()
        with self.store.tracer.span("ckpt.restore") as sp:
            return self._restore(step, sp)

    def _restore(self, step: Optional[int], sp):
        if step is None:
            step = self.latest_step()
        if step is None:
            return None
        man = self.store.get(f"{self.cfg.prefix}/MANIFEST.{step}")
        if man is None:
            return None
        m = json.loads(man)
        if m.get("format") == "range" and step != self.latest_step():
            return None  # stable keys already carry later steps' ranges
        sp.count(leaves=len(m["leaves"]))
        span = self.store.tracer.span
        out = {}
        crcs = m.get("leaf_crc", {})
        for name in m["leaves"]:
            key = f"{self.cfg.prefix}/data{name}" \
                if m.get("format") == "range" \
                else f"{self.cfg.prefix}/data/{step}{name}"
            with span("ckpt.read") as sp:
                raw = self.store.get(key)
                sp.count(nbytes=len(raw) if raw is not None else 0)
            if raw is None:
                return None
            with span("ckpt.decode", nbytes=len(raw)):
                if m.get("format") == "range" and name in crcs \
                        and (zlib.crc32(raw) & 0xFFFFFFFF) != crcs[name]:
                    # a crash mid-save left partial range patches of a
                    # NEWER step on the stable key: the set is
                    # unrestorable — fail loudly rather than hand back
                    # silently corrupt tensors
                    return None
                out[name] = _decode_leaf(raw)
        return out, m


def unflatten_into(template: Any, flat: Dict[str, np.ndarray],
                   prefix: str = ""):
    """Rebuild a pytree shaped like `template` from restore() output."""
    if isinstance(template, dict):
        return {k: unflatten_into(v, flat, f"{prefix}/{k}")
                for k, v in template.items()}
    if isinstance(template, (list, tuple)):
        t = [unflatten_into(v, flat, f"{prefix}/{i}")
             for i, v in enumerate(template)]
        return type(template)(t) if isinstance(template, tuple) else t
    return flat[prefix]
