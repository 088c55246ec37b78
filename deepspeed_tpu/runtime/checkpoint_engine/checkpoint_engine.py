"""Checkpoint I/O engines.

Parity with reference ``runtime/checkpoint_engine/checkpoint_engine.py:9-28``
(``CheckpointEngine`` ABC: create/save/load/commit) — the Orbax engine plays
both the Torch role (synchronous) and the Nebula role (async tiered save)
since Orbax natively does async, sharded, resharding-on-load checkpoints.
"""

import os
import pickle
from abc import ABC, abstractmethod

from deepspeed_tpu.monitor.trace import span
from deepspeed_tpu.runtime.fault import inject
from deepspeed_tpu.runtime.fault.atomic import atomic_write_bytes
from deepspeed_tpu.utils.logging import logger


class CheckpointEngine(ABC):
    """create/save/load/commit protocol.  ``save`` takes the device-array
    pytree and a picklable metadata dict separately — array leaves go through
    the sharded writer, metadata through pickle."""

    def __init__(self, config_params=None):
        self.config_params = config_params

    def create(self, tag):
        logger.info(f"[ckpt] checkpoint tag {tag} begin")

    @abstractmethod
    def save(self, arrays, meta, path: str):
        ...

    @abstractmethod
    def load(self, path: str, abstract_arrays=None):
        """Returns (arrays, meta).  ``abstract_arrays`` (ShapeDtypeStruct tree
        with shardings) enables resharding-on-load."""
        ...

    @abstractmethod
    def commit(self, tag):
        ...


class OrbaxCheckpointEngine(CheckpointEngine):
    """Sharded, optionally async save/restore of jax.Array pytrees.

    Restoring onto a different mesh/sharding reshapes automatically — this
    single mechanism covers the reference's ZeRO-shard merging
    (``zero_to_fp32.py:459``), universal-checkpoint resharding
    (``deepspeed/checkpoint/``), and elastic world-size changes.
    """

    def __init__(self, config_params=None, use_async=False):
        super().__init__(config_params)
        # seconds on a process's first engine: orbax pulls in the cloud
        # logging stack (a named part of the engine's set-up)
        with span("dstpu.setup.lazy_import", cat="setup",
                  module="orbax.checkpoint"):
            import orbax.checkpoint as ocp
        self._ocp = ocp
        self.use_async = use_async
        self._ckptr = None
        # async mode: (path, pickled meta) pairs whose durability is
        # deferred to commit() — metadata must never land before the
        # array shards it describes (see save())
        self._pending_meta = []

    def _checkpointer(self):
        if self._ckptr is None:
            self._ckptr = self._ocp.StandardCheckpointer()
        return self._ckptr

    def save(self, arrays, meta, path):
        path = os.path.abspath(path)
        if arrays is not None:
            ckptr = self._checkpointer()
            ckptr.save(os.path.join(path, "arrays"), arrays, force=True)
            if not self.use_async:
                ckptr.wait_until_finished()
        inject.fire("ckpt.arrays_write", path=path)
        os.makedirs(path, exist_ok=True)
        meta_bytes = pickle.dumps(meta)
        if self.use_async and arrays is not None:
            # async-save ordering: the array shards are NOT yet durable
            # here.  Writing meta.pkl now would let a crash between the
            # two leave a metadata-complete but data-incomplete
            # checkpoint — durability is established only at commit(),
            # after wait_until_finished()
            self._pending_meta.append((path, meta_bytes))
            return
        # temp-file + os.replace: a crash mid-write must never leave a
        # truncated meta.pkl shadowing the real one
        atomic_write_bytes(os.path.join(path, "meta.pkl"), meta_bytes)

    def load(self, path, abstract_arrays=None):
        path = os.path.abspath(path)
        meta = {}
        meta_path = os.path.join(path, "meta.pkl")
        if os.path.exists(meta_path):
            with open(meta_path, "rb") as f:
                meta = pickle.load(f)
        arrays = None
        arrays_path = os.path.join(path, "arrays")
        if os.path.isdir(arrays_path):
            arrays = self._checkpointer().restore(arrays_path, abstract_arrays)
        return arrays, meta

    def metadata(self, path):
        """Shapes/dtypes of the saved arrays (no data read) — lets a FRESH
        engine build device-agnostic restore targets, so a checkpoint saved
        by a different process/device topology (e.g. 2 hosts × 4 chips)
        loads on the current one (1 host × 8): Orbax otherwise restores
        onto the devices recorded at save time."""
        arrays_path = os.path.join(os.path.abspath(path), "arrays")
        if not os.path.isdir(arrays_path):
            return None
        md = self._checkpointer().metadata(arrays_path)
        # unwrap StepMetadata/TreeMetadata to the plain ArrayMetadata pytree
        item = getattr(md, "item_metadata", md)
        return getattr(item, "tree", item)

    def commit(self, tag):
        if self._ckptr is not None:
            self._ckptr.wait_until_finished()
        # arrays are durable now — publish the deferred metadata (async
        # mode; empty list in sync mode).  Entries whose staging dir has
        # vanished belong to an earlier save that aborted and was GC'd:
        # drop them with a warning rather than failing THIS commit
        pending, self._pending_meta = self._pending_meta, []
        for path, meta_bytes in pending:
            if not os.path.isdir(path):
                logger.warning(f"[ckpt] dropping deferred metadata for "
                               f"vanished save at {path} (aborted save?)")
                continue
            atomic_write_bytes(os.path.join(path, "meta.pkl"), meta_bytes)
        logger.info(f"[ckpt] checkpoint tag {tag} committed")
        return True


# Parity alias: the reference's torch engine (synchronous save) — same class,
# synchronous mode.
class TorchCheckpointEngine(OrbaxCheckpointEngine):

    def __init__(self, config_params=None):
        super().__init__(config_params, use_async=False)


# Parity alias: Nebula async tiered save → orbax async mode.
class NebulaCheckpointEngine(OrbaxCheckpointEngine):

    def __init__(self, config_params=None):
        super().__init__(config_params, use_async=True)
