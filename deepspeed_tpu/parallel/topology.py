"""Device-mesh topology: the TPU-native replacement for process groups.

The reference builds NCCL communicators per parallelism axis
(``deepspeed/utils/groups.py:59,108,202`` for model/expert/expert-data groups,
``deepspeed/runtime/pipe/topology.py:12,251`` for the pipeline rank grid).
On TPU the same capability is one ``jax.sharding.Mesh`` whose named axes ARE
the groups: collectives take an axis name instead of a communicator handle,
and XLA lays the collective onto ICI/DCN from the mesh's device order.

Axis order (outermost → innermost): ``pp, edp, ep, sp, tp``.
``tp`` is innermost so tensor-parallel collectives ride the fastest ICI links;
``pp`` is outermost so pipeline stages land on DCN-adjacent slices in
multi-host meshes.  The data-parallel "group" is the compound axis
``(edp, ep)`` — when expert parallelism is enabled, ``ep`` carves expert
groups out of the DP world exactly like the reference
(``groups.py:108 _create_expert_and_data_parallel``).
"""

from dataclasses import dataclass, field

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu.utils.logging import logger

# Canonical axis names.
PP_AXIS = "pp"      # pipeline stages
MDP_AXIS = "mdp"    # MiCS replica groups (ZeRO shards live WITHIN a group,
                    # replicate ACROSS this axis — reference mics.py:24-29)
EDP_AXIS = "edp"    # expert-data-parallel (DP within an expert group)
EP_AXIS = "ep"      # expert parallel
SP_AXIS = "sp"      # sequence/context parallel
TP_AXIS = "tp"      # tensor/model parallel

AXIS_ORDER = (PP_AXIS, MDP_AXIS, EDP_AXIS, EP_AXIS, SP_AXIS, TP_AXIS)

# Compound groups, named for parity with the reference group getters.
DP_AXES = (MDP_AXIS, EDP_AXIS, EP_AXIS)    # dense data-parallel group
DENSE_GRAD_AXES = (MDP_AXIS, EDP_AXIS, EP_AXIS, SP_AXIS)  # grad axes, dense
EXPERT_GRAD_AXES = (MDP_AXIS, EDP_AXIS, SP_AXIS)          # grad axes, expert


@dataclass
class ParallelTopology:
    """A named device mesh plus the group algebra DeepSpeed exposes.

    Analog of ``PipeModelDataParallelTopology`` (reference
    ``runtime/pipe/topology.py:244``) generalized with expert and sequence
    axes.
    """

    dp: int = 1
    tp: int = 1
    pp: int = 1
    ep: int = 1
    sp: int = 1
    mdp: int = 1
    devices: list = field(default=None, repr=False)
    mesh: Mesh = field(default=None, repr=False)

    def __post_init__(self):
        if self.dp % (self.ep * self.mdp) != 0:
            raise ValueError(
                f"expert parallel size {self.ep} x MiCS replica groups "
                f"{self.mdp} must divide data parallel size {self.dp}")
        self.edp = self.dp // (self.ep * self.mdp)
        devices = self.devices
        if devices is None:
            devices = jax.devices()
        need = self.world_size
        if len(devices) < need:
            raise ValueError(
                f"topology dp={self.dp} tp={self.tp} pp={self.pp} sp={self.sp} "
                f"needs {need} devices, have {len(devices)}")
        devices = devices[:need]
        # which of the two below laid the devices out ("caller" = a mesh
        # was handed in) — a plain reshape ignores the physical torus
        self.mesh_built_by = "caller"
        if self.mesh is None:
            shape = (self.pp, self.mdp, self.edp, self.ep, self.sp, self.tp)
            try:
                from jax.experimental import mesh_utils
                dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
                self.mesh_built_by = "mesh_utils.create_device_mesh"
            except Exception as e:
                logger.warning(
                    f"mesh_utils.create_device_mesh refused shape {shape} "
                    f"({type(e).__name__}: {e}); laying the devices out by "
                    f"plain reshape")
                dev_array = np.asarray(devices).reshape(shape)
                self.mesh_built_by = "reshape"
            self.mesh = Mesh(dev_array, AXIS_ORDER)

    # ------------------------------------------------------------------ #
    @property
    def world_size(self):
        return self.pp * self.mdp * self.edp * self.ep * self.sp * self.tp

    # Group getters — parity with reference ``utils/groups.py:280-392``.
    def get_data_parallel_axes(self):
        return DP_AXES

    def get_model_parallel_axes(self):
        return (TP_AXIS,)

    def get_pipe_parallel_axes(self):
        return (PP_AXIS,)

    def get_expert_parallel_axes(self):
        return (EP_AXIS,)

    def get_expert_data_parallel_axes(self):
        # the DP replicas of one expert: the MiCS replica axis is part of
        # the group, else expert grads would never reduce across groups
        return (MDP_AXIS, EDP_AXIS)

    def get_sequence_parallel_axes(self):
        return (SP_AXIS,)

    def axis_size(self, name):
        return self.mesh.shape[name]

    def get_data_parallel_world_size(self):
        return self.dp

    def get_model_parallel_world_size(self):
        return self.tp

    def get_pipe_parallel_world_size(self):
        return self.pp

    def get_sequence_parallel_world_size(self):
        return self.sp

    def get_expert_parallel_world_size(self):
        return self.ep

    # ------------------------------------------------------------------ #
    def batch_spec(self, extra_dims=0):
        """PartitionSpec for a [batch, ...] array: batch sharded over DP
        (and sequence over sp when present on dim 1)."""
        dims = [DENSE_GRAD_AXES if self.dp > 1 or self.ep > 1 else None]
        if self.sp > 1:
            # With an active sp axis the batch dim carries (edp, ep) only and
            # dim 1 (sequence) carries sp.
            dims = [DP_AXES, SP_AXIS]
        return P(*dims, *([None] * extra_dims))

    def data_spec(self, batch_sharded=True, seq_dim=None):
        """Spec for input batches: dim0 over DP; optional seq dim over sp."""
        parts = [DP_AXES if batch_sharded else None]
        if seq_dim == 1:
            parts.append(SP_AXIS if self.sp > 1 else None)
        return P(*parts)

    def replicated_spec(self):
        return P()

    # ------------------------------------------------------------------ #
    # Introspection hooks — used by the comm-cost analyzer, the sharding
    # lint's registry tests, and the PartitionSpec-helper placement tests.
    # ------------------------------------------------------------------ #
    def axis_sizes(self):
        """``{axis: size}`` of the live mesh (all six canonical axes)."""
        return {k: int(v) for k, v in self.mesh.shape.items()}

    def shard_shape(self, spec, global_shape):
        """Per-device shard shape a ``PartitionSpec`` produces for a
        global array shape on THIS mesh — the statically checkable
        ground truth the spec helpers are validated against (a replicated
        batch dim shows up here as a full-size shard on every device)."""
        return NamedSharding(self.mesh, spec).shard_shape(
            tuple(global_shape))

    def shards_per_device(self, spec, global_shape):
        """Fraction of a global array each device holds under ``spec``
        (1.0 = fully replicated — the TL010 smell, numerically)."""
        shard = self.shard_shape(spec, global_shape)
        total = float(np.prod(global_shape)) or 1.0
        return float(np.prod(shard)) / total


# --------------------------------------------------------------------- #
# Global topology registry — analog of the module-level group cache in
# reference ``utils/groups.py``.
# --------------------------------------------------------------------- #
_TOPOLOGY = None


def initialize_topology(dp=None, tp=1, pp=1, ep=1, sp=1, mics=0,
                        devices=None):
    """``mics`` > 0 sizes the ZeRO shard group (reference
    ``mics_shard_size``, ``runtime/zero/mics.py:54``): the DP world splits
    into ``mdp`` replica groups of ``mics`` ZeRO-sharding devices each —
    params/opt-state shard WITHIN a group (ICI-local gathers), replicate
    ACROSS groups; grads still reduce over all of DP."""
    global _TOPOLOGY
    if devices is None:
        devices = jax.devices()
    if dp is None:
        denom = tp * pp * ep * sp
        if len(devices) % denom != 0:
            raise ValueError(
                f"device count {len(devices)} not divisible by tp*pp*ep*sp={denom}")
        dp = (len(devices) // denom) * ep  # dp includes the ep sub-axis
    mdp = 1
    if mics and mics > 0:
        edp_world = dp // ep
        if edp_world % mics != 0:
            raise ValueError(
                f"mics_shard_size={mics} must divide the expert-data-"
                f"parallel world {edp_world} (dp={dp} / ep={ep})")
        mdp = edp_world // mics
    _TOPOLOGY = ParallelTopology(dp=dp, tp=tp, pp=pp, ep=ep, sp=sp, mdp=mdp,
                                 devices=devices)
    return _TOPOLOGY


def get_topology():
    global _TOPOLOGY
    if _TOPOLOGY is None:
        _TOPOLOGY = initialize_topology()
    return _TOPOLOGY


def set_topology(topo):
    global _TOPOLOGY
    _TOPOLOGY = topo
    return _TOPOLOGY


def reset_topology():
    global _TOPOLOGY
    _TOPOLOGY = None
