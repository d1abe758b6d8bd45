"""Mesh topology for hybrid parallelism.

TPU-native equivalent of the reference's rank-mesh machinery
(reference: python/paddle/distributed/fleet/base/topology.py:35
CommunicateTopology — an N-d cartesian rank mesh, :116
HybridCommunicateGroup — one comm group per axis). Here the mesh is a
jax.sharding.Mesh whose named axes ride ICI; "comm group per axis" becomes
"collectives over a named mesh axis", and the reference's ring_id plumbing
disappears into GSPMD.

Axis naming convention (order matters for ICI locality: fastest-varying
last): ("pp", "dp", "sharding", "sep", "mp") — model parallel innermost so
its collectives ride the shortest ICI links, matching the reference's
hybrid order data>pipe>sharding>model (topology.py:57).
"""

from __future__ import annotations

import itertools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

_HYBRID_AXES = ("pp", "dp", "sharding", "sep", "mp")


def build_device_array(shape: Tuple[int, ...], devices=None,
                       topology_aware: Optional[bool] = None):
    """Topology-aware device placement for a mesh of ``shape``.

    The reference hand-tunes NCCL ring order for its hybrid groups
    (platform/nccl_helper.h:190, sharding_optimizer.py:968); the TPU
    analog is laying mesh axes onto the physical ICI torus. A naive
    ``reshape(jax.devices())`` keeps enumeration order, which on a real
    torus (e.g. v4-64) can put the innermost (mp) axis on non-adjacent
    chips. ``mesh_utils.create_device_mesh`` solves the assignment so
    later axes land on the tightest physical loops; on multi-slice
    deployments ``create_hybrid_device_mesh`` puts the leading axes
    (pp/dp) on DCN and the rest on ICI.

    Returns (device_array, assignment_tag) where the tag records which
    strategy was used: "hybrid_dcn", "topology_aware", or
    "enumeration_order" (explicit devices= / non-TPU fallback).

    ``topology_aware`` overrides the default policy (None = solve the
    assignment only when the caller did not fix an explicit device
    order): True forces the solver on an explicit TPU device list (the
    AOT scale proof passes compile-only topology devices), False forces
    plain reshape.
    """
    import math

    explicit = devices is not None
    devices = list(devices if devices is not None else jax.devices())
    need = int(np.prod(shape))
    devices = devices[:need]
    if topology_aware is None:
        topology_aware = not explicit
    if not topology_aware or devices[-1].platform != "tpu":
        # Explicit order is the caller's contract; non-TPU (the virtual
        # CPU test mesh) has no physical topology to exploit.
        return np.asarray(devices).reshape(shape), "enumeration_order"

    from jax.experimental import mesh_utils

    slice_ids = {getattr(d, "slice_index", 0) for d in devices}
    n_slices = len(slice_ids)
    if n_slices > 1:
        # Factor the slice count onto the leading (outermost) axes —
        # those are dp/pp in the hybrid order, whose collectives
        # tolerate DCN latency; mp/sep stay intra-slice on ICI.
        dcn = [1] * len(shape)
        remaining = n_slices
        for i, dim in enumerate(shape):
            f = math.gcd(dim, remaining)
            dcn[i] = f
            remaining //= f
            if remaining == 1:
                break
        if remaining == 1:
            try:
                arr = mesh_utils.create_hybrid_device_mesh(
                    tuple(s // d for s, d in zip(shape, dcn)), tuple(dcn),
                    devices=devices)
                return arr, "hybrid_dcn"
            except (ValueError, AssertionError, NotImplementedError):
                pass
    try:
        arr = mesh_utils.create_device_mesh(shape, devices=devices)
        return arr, "topology_aware"
    except (ValueError, AssertionError, NotImplementedError):
        pass
    arr = _solve_per_core_mesh(shape, devices)
    if arr is not None:
        return arr, "topology_aware"
    return np.asarray(devices).reshape(shape), "enumeration_order"


def _solve_per_core_mesh(shape: Tuple[int, ...], devices):
    """create_device_mesh refuses per-TensorCore v4+ device lists (it
    wants megacore, one device per chip) — but compile-only topologies
    (jax.experimental.topologies) expose 2 cores/chip. Solve the
    assignment at CHIP level with one representative core per chip, then
    expand each chip into its cores along the innermost axis, so sibling
    cores are always mp-neighbors (hop 0) and the chip-level solve fixes
    the ICI layout. Returns None when the structure doesn't apply."""
    from collections import defaultdict

    from jax.experimental import mesh_utils

    by_chip = defaultdict(list)
    for d in devices:
        coords = getattr(d, "coords", None)
        if coords is None:
            return None
        by_chip[tuple(coords)].append(d)
    counts = {len(v) for v in by_chip.values()}
    if len(counts) != 1:
        return None
    cpc = counts.pop()
    if cpc == 1 or shape[-1] % cpc != 0:
        return None
    for chip in by_chip.values():
        chip.sort(key=lambda d: getattr(d, "core_on_chip", d.id))
    chip_shape = shape[:-1] + (shape[-1] // cpc,)
    reps = [chip[0] for chip in by_chip.values()]
    try:
        chip_mesh = mesh_utils.create_device_mesh(chip_shape, devices=reps)
    except (ValueError, AssertionError, NotImplementedError):
        return None
    out = np.empty(shape, dtype=object)
    flat_out = out.reshape(-1, shape[-1])
    flat_chip = chip_mesh.reshape(-1, chip_shape[-1])
    for row in range(flat_out.shape[0]):
        for j in range(chip_shape[-1]):
            cores = by_chip[tuple(flat_chip[row, j].coords)]
            for k in range(cpc):
                flat_out[row, j * cpc + k] = cores[k]
    return out


def mesh_axis_locality(dev_array: "np.ndarray", axis_names=None) -> Dict:
    """Physical ICI locality per mesh axis: mean/max chip-torus hop
    between consecutive devices along each axis (wrap link included for
    rings longer than 2). Two TensorCores of one chip are hop 0. Returns
    {} when devices carry no coords (CPU/virtual meshes)."""
    devs = dev_array.ravel()
    if not hasattr(devs[0], "coords") or devs[0].coords is None:
        return {}
    coords = np.asarray([d.coords for d in devs]).reshape(
        dev_array.shape + (-1,))
    bounds = coords.reshape(-1, coords.shape[-1]).max(axis=0) + 1

    def hop(a, b, wrap_ok):
        # Torus wraparound credit only in dimensions the LINE actually
        # spans end-to-end: a mesh axis laid along a sub-block of a
        # wider physical ring has no wrap link of its own, and counting
        # one would understate the distance (and let the scale proof's
        # max-hop assertion pass for a non-adjacent placement).
        d = np.abs(a - b)
        wrapped = np.where(wrap_ok, np.minimum(d, bounds - d), d)
        return int(wrapped.sum())

    names = axis_names or [f"axis{i}" for i in range(dev_array.ndim)]
    out = {}
    for ax, name in enumerate(names):
        n = dev_array.shape[ax]
        if n == 1:
            continue
        lines = np.moveaxis(coords, ax, 0).reshape(n, -1, coords.shape[-1])
        hops = []
        for line_idx in range(lines.shape[1]):
            line = lines[:, line_idx]
            wrap_ok = np.array([
                len(set(line[:, dim])) == bounds[dim]
                for dim in range(line.shape[1])])
            pairs = [(i, i + 1) for i in range(n - 1)]
            if n > 2:
                pairs.append((n - 1, 0))  # ring wrap link
            hops.extend(hop(line[i], line[j], wrap_ok)
                        for i, j in pairs)
        out[name] = {"mean_hop": round(float(np.mean(hops)), 3),
                     "max_hop": int(np.max(hops)), "size": n}
    return out


class CommunicateTopology:
    """N-d cartesian topology over ranks (device indices)."""

    def __init__(self, hybrid_group_names: Sequence[str] =
                 ("data", "pipe", "sharding", "model"),
                 dims: Sequence[int] = (1, 1, 1, 1)):
        self._parallel_names = list(hybrid_group_names)
        self._dims = list(dims)
        self.coordinate = list(itertools.product(
            *(range(d) for d in self._dims)))
        self._coord2rank = {c: i for i, c in enumerate(self.coordinate)}

    def get_hybrid_group_names(self) -> List[str]:
        return self._parallel_names

    def get_dim(self, axis_name: str) -> int:
        return self._dims[self._parallel_names.index(axis_name)]

    get_dim_size = get_dim

    def world_size(self) -> int:
        return int(np.prod(self._dims))

    def get_rank(self, **kwargs) -> int:
        coord = tuple(kwargs[name] for name in self._parallel_names)
        return self._coord2rank[coord]

    def get_coord(self, rank: int):
        return self.coordinate[rank]

    def get_axis_list(self, axis_name: str, index: int) -> List[int]:
        axis = self._parallel_names.index(axis_name)
        return [r for r, c in enumerate(self.coordinate)
                if c[axis] == index]

    def get_comm_list(self, axis_name: str) -> List[List[int]]:
        axis = self._parallel_names.index(axis_name)
        other = [i for i in range(len(self._dims)) if i != axis]
        groups = []
        for fixed in itertools.product(*(range(self._dims[i])
                                         for i in other)):
            group = []
            for v in range(self._dims[axis]):
                coord = list(fixed)
                coord.insert(axis, v)
                group.append(self._coord2rank[tuple(coord)])
            groups.append(group)
        return groups

    def get_rank_from_stage(self, global_rank: int, **kwargs) -> int:
        coord = list(self.get_coord(global_rank))
        for k, v in kwargs.items():
            coord[self._parallel_names.index(k)] = v
        return self._coord2rank[tuple(coord)]


class HybridCommunicateGroup:
    """Builds the device mesh + per-axis views for dp/mp/pp/sharding/sep.

    Reference: topology.py:116 HybridCommunicateGroup (one NCCL group per
    axis per index) — here one jax Mesh; "groups" are just named axes.
    """

    def __init__(self, dp_degree: int = 1, mp_degree: int = 1,
                 pp_degree: int = 1, sharding_degree: int = 1,
                 sep_degree: int = 1, devices=None,
                 topology_aware: Optional[bool] = None):
        avail = list(devices) if devices is not None else jax.devices()
        need = dp_degree * mp_degree * pp_degree * sharding_degree * \
            sep_degree
        if need > len(avail):
            raise ValueError(
                f"hybrid degrees {need} exceed device count {len(avail)}")
        self.dims = {"pp": pp_degree, "dp": dp_degree,
                     "sharding": sharding_degree, "sep": sep_degree,
                     "mp": mp_degree}
        shape = tuple(self.dims[a] for a in _HYBRID_AXES)
        dev_array, self.mesh_assignment = build_device_array(
            shape, avail if devices is not None else None, topology_aware)
        self.mesh = Mesh(dev_array, _HYBRID_AXES)
        self.topology = CommunicateTopology(
            ("pipe", "data", "sharding", "sep", "model"), shape)
        self.global_rank = 0  # SPMD: per-device coords live in the mesh
        self.nranks = need

    # -- reference-compatible accessors ---------------------------------------

    def get_parallel_mode(self) -> str:
        if self.dims["pp"] > 1:
            return "pipeline"
        if self.dims["sharding"] > 1:
            return "sharding_parallel"
        if self.dims["mp"] > 1:
            return "tensor_parallel"
        return "data_parallel"

    def get_data_parallel_world_size(self) -> int:
        return self.dims["dp"]

    def get_model_parallel_world_size(self) -> int:
        return self.dims["mp"]

    def get_pipe_parallel_world_size(self) -> int:
        return self.dims["pp"]

    def get_sharding_parallel_world_size(self) -> int:
        return self.dims["sharding"]

    def get_sep_parallel_world_size(self) -> int:
        return self.dims["sep"]

    # axis names for collectives inside shard_map/pjit
    def get_data_parallel_group(self) -> str:
        return "dp"

    def get_model_parallel_group(self) -> str:
        return "mp"

    def get_pipe_parallel_group(self) -> str:
        return "pp"

    def get_sharding_parallel_group(self) -> str:
        return "sharding"

    def get_sep_parallel_group(self) -> str:
        return "sep"

    def get_check_parallel_group(self) -> str:
        return "mp"

    def named_sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, PartitionSpec(*spec))


_HCG: Optional[HybridCommunicateGroup] = None


def set_hybrid_communicate_group(hcg: HybridCommunicateGroup) -> None:
    global _HCG
    _HCG = hcg


def get_hybrid_communicate_group() -> Optional[HybridCommunicateGroup]:
    return _HCG


def create_hybrid_communicate_group(dp_degree=1, mp_degree=1, pp_degree=1,
                                    sharding_degree=1, sep_degree=1,
                                    devices=None) -> HybridCommunicateGroup:
    hcg = HybridCommunicateGroup(dp_degree, mp_degree, pp_degree,
                                 sharding_degree, sep_degree, devices)
    set_hybrid_communicate_group(hcg)
    return hcg


def make_mesh(axis_shapes: Dict[str, int], devices=None) -> Mesh:
    """Generic mesh builder for custom axis layouts (topology-aware when
    the caller does not fix an explicit device order)."""
    names = tuple(axis_shapes)
    shape = tuple(axis_shapes[n] for n in names)
    dev_array, _ = build_device_array(shape, devices)
    return Mesh(dev_array, names)


# The serving mesh's user-facing "model" axis IS the fleet's mp axis:
# naming it "mp" lets the GPT weight PartitionSpecs that mp_layers.py
# already annotates (P(None, "mp") column, P("mp", None) row/vocab)
# apply to the decode engine verbatim — one pspec convention for
# training and serving instead of a parallel serving-only one.
SERVING_MODEL_AXIS = "mp"


def collectives_in(compiled_text: str) -> Dict[str, int]:
    """Collective ops in a compiled program's text, by kind (the SPMD
    partitioner inserts them at compile time, so this reads
    ``compiled.as_text()``, not the lowering)."""
    import collections
    import re
    return dict(collections.Counter(re.findall(
        r"\b(all-reduce|all-gather|reduce-scatter|collective-permute|"
        r"all-to-all)(?:-start)?\(", compiled_text)))


def make_serving_mesh(model_parallel: int, devices=None) -> Mesh:
    """1-D tensor-parallel mesh for the decode engine / serving stack:
    ``model_parallel`` devices along the :data:`SERVING_MODEL_AXIS`
    axis. The same ``make_mesh`` path the fleet side uses, so a
    deployment that trains on an mp mesh serves on the identical
    layout (topology-aware placement included). ``model_parallel=1``
    is the graceful-degradation mesh: every sharding it produces is
    replicated, and engine outputs match the mesh-less path."""
    mp = int(model_parallel)
    if mp < 1:
        raise ValueError(f"model_parallel must be >= 1, got {mp}")
    avail = len(devices) if devices is not None else len(jax.devices())
    if mp > avail:
        raise ValueError(
            f"serving mesh model={mp} exceeds device count {avail}")
    return make_mesh({SERVING_MODEL_AXIS: mp}, devices=devices)


def parse_mesh_spec(spec) -> int:
    """Parse the serving CLI's ``--mesh`` value to a model-parallel
    degree: ``"model=N"`` (the documented form), ``"mp=N"`` (the
    underlying axis name), or a bare ``"N"``. Raises ValueError on
    anything else — the CLI surfaces it as a typed argument error, not
    a confusing mesh-construction failure later."""
    s = str(spec).strip()
    if "=" in s:
        key, _, val = s.partition("=")
        if key.strip() not in ("model", SERVING_MODEL_AXIS):
            raise ValueError(
                f"--mesh axis must be 'model' (or "
                f"{SERVING_MODEL_AXIS!r}), got {key.strip()!r}")
        s = val.strip()
    try:
        n = int(s)
    except ValueError:
        raise ValueError(
            f"--mesh expects 'model=N' or a bare integer, got {spec!r}")
    if n < 1:
        raise ValueError(f"--mesh model={n} must be >= 1")
    return n


def filter_pspec(pspec, mesh: Mesh) -> PartitionSpec:
    """Project a PartitionSpec onto ``mesh``: axis names the mesh does
    not carry are dropped (that dimension replicates). The hybrid-mesh
    pspecs name up to five axes (dp/mp/pp/sharding/sep); a serving
    mesh carries only ``mp``, and a weight annotated P(None, "mp")
    must mean "shard on mp, ignore the rest" there rather than fail."""
    if pspec is None:
        return PartitionSpec()
    axes = set(mesh.axis_names)

    def keep(entry):
        if entry is None:
            return None
        if isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in axes)
            if not kept:
                return None
            return kept if len(kept) > 1 else kept[0]
        return entry if entry in axes else None

    return PartitionSpec(*(keep(e) for e in tuple(pspec)))
