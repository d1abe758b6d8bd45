"""Hybrid pipeline lowering and mesh locality on the virtual CPU mesh.

The scale proofs that compile for a described TPU pod (10B on v4-64,
the topology-aware mesh solver) live in tests/test_tpu_aot_compile.py,
the one file that may describe a TPU topology.
"""

import numpy as np
import pytest


def test_abstract_pipeline_lower_tiny():
    """The abstract=True path itself (no materialization) on the virtual
    CPU mesh: lower a tiny hybrid config and check input placements."""
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    import paddle_tpu.optimizer as optim
    from paddle_tpu.distributed.topology import HybridCommunicateGroup
    from paddle_tpu.models import gpt_tiny
    from paddle_tpu.models.gpt_pipeline import GPTPipelineTrainStep

    hcg = HybridCommunicateGroup(mp_degree=2, pp_degree=2,
                                 sharding_degree=2,
                                 devices=jax.devices()[:8])
    cfg = gpt_tiny()
    step = GPTPipelineTrainStep(
        cfg, optim.AdamW(learning_rate=1e-4), pp=2, n_micro=2, hcg=hcg,
        zero_axis="sharding", schedule="1f1b", abstract=True)
    # nothing materialized
    assert all(isinstance(v, jax.ShapeDtypeStruct)
               for v in step.stacked.values())
    lowered = step.lower(8, 64)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert int(mem.temp_size_in_bytes) > 0


def test_mesh_locality_empty_on_cpu():
    import jax

    from paddle_tpu.distributed.topology import (build_device_array,
                                                 mesh_axis_locality)

    arr, tag = build_device_array((2, 4), None)
    assert tag == "enumeration_order"  # virtual CPU: no topology
    assert mesh_axis_locality(arr, ["a", "b"]) == {}


def test_mesh_locality_no_phantom_wrap():
    """A mesh axis laid along a sub-range of a wider torus dimension has
    no wraparound link of its own: the wrap pair must be charged the
    absolute distance (regression: torus-wrap credit understated hops
    and could let the mp-adjacency assertion pass wrongly)."""
    from paddle_tpu.distributed.topology import mesh_axis_locality

    class D:
        def __init__(self, *c):
            self.coords = list(c)

    # x-dim bound is 8 (second row reaches 7); the first row's line runs
    # x=0..5 only -> its wrap pair (5,0) is 5 hops, not min(5, 3)=3
    row0 = [D(x, 0) for x in range(6)]
    row1 = [D(x + 2, 1) for x in range(6)]
    arr = np.asarray([row0, row1], dtype=object)
    loc = mesh_axis_locality(arr, ["outer", "ring"])
    assert loc["ring"]["max_hop"] == 5, loc
    # a line spanning the FULL dimension keeps its genuine wrap link
    full = np.asarray([[D(x, 0) for x in range(8)]], dtype=object)
    loc2 = mesh_axis_locality(full, ["o", "ring"])
    assert loc2["ring"]["max_hop"] == 1, loc2
