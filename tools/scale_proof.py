"""AOT scale proof for the north-star config (BASELINE.json config 4):
compile the ERNIE-3.0-10B-class hybrid train step (mp x pp x sharding)
against a TPU v4-64 topology and assert per-device HBM fit.

No TPU pod is needed: jax.experimental.topologies builds a compile-only
PJRT topology (libtpu does the real XLA:TPU compile), and the compiled
executable's memory analysis gives exact per-device argument/temp bytes.
This is the TPU-native analog of what the reference can only discover by
launching on the cluster (fleet sharding_optimizer.py:87 decides
placements at program-build time but memory fit is found out at run
time; here the AOT artifact proves it before any chip is touched).

Topology note: compile-only v4 devices are per-TensorCore (two per
chip, no megacore fusion), so ``v4:2x4x4`` = 32 chips = 64 cores =
"v4-64". The budget asserted is the per-core share, 16 GiB (32 GiB HBM
per chip / 2 cores) — conservative vs a megacore deployment, which
would see the full 32 GiB per device.

Usage: python tools/scale_proof.py [--out SCALE_PROOF.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# The few concrete buffers built during model construction (position ids
# etc.) should land on host — the TPU topology here is compile-only.
# Off-cloud, libtpu's GCP metadata probing retries for ~8 minutes before
# failing; compile-only use never needs it.
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "true")
if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

GIB = 1024 ** 3

# v4 HBM: 32 GiB per chip, 2 TensorCores per chip in compile-only mode.
V4_HBM_PER_CORE = 16 * GIB


def _mem_bytes(compiled):
    """Per-device byte accounting from the compiled memory analysis.
    Donated params+slots alias their outputs; live bytes per device are
    arguments (params/slots/batch) + temps + non-aliased outputs +
    code. ONE definition — both proofs must agree on "fits"."""
    mem = compiled.memory_analysis()
    arg_b = int(mem.argument_size_in_bytes)
    out_b = int(mem.output_size_in_bytes)
    temp_b = int(mem.temp_size_in_bytes)
    alias_b = int(mem.alias_size_in_bytes)
    code_b = int(mem.generated_code_size_in_bytes)
    live = arg_b + temp_b + max(0, out_b - alias_b) + code_b
    return arg_b, out_b, temp_b, alias_b, code_b, live


def _restores_hcg(fn):
    """run_proof sets the GLOBAL hybrid group to an abstract TPU
    topology (build_step needs it set during lowering); restore the
    caller's group afterwards — leaking a 64-device TPU mesh poisons
    every later sharding-constraint in the process (observed as
    cross-test-file failures)."""
    import functools

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        from paddle_tpu.distributed.topology import (
            get_hybrid_communicate_group, set_hybrid_communicate_group)
        prev = get_hybrid_communicate_group()
        try:
            return fn(*args, **kwargs)
        finally:
            set_hybrid_communicate_group(prev)
    return wrapper


def build_step(mp: int, pp: int, sharding: int, n_micro: int,
               devices, schedule: str = "1f1b"):
    """Abstract 10B hybrid train step over the given devices."""
    import paddle_tpu.optimizer as optim
    from paddle_tpu.distributed.topology import (
        HybridCommunicateGroup, set_hybrid_communicate_group)
    from paddle_tpu.models.gpt import ernie_10b
    from paddle_tpu.models.gpt_pipeline import GPTPipelineTrainStep

    hcg = HybridCommunicateGroup(
        mp_degree=mp, pp_degree=pp, sharding_degree=sharding,
        devices=devices, topology_aware=True)
    set_hybrid_communicate_group(hcg)
    cfg = ernie_10b(dropout=0.0, attn_dropout=0.0, dtype="bfloat16",
                    loss_chunk_size=512)
    step = GPTPipelineTrainStep(
        cfg, optim.AdamW(learning_rate=1e-4), pp=pp, n_micro=n_micro,
        hcg=hcg, zero_axis="sharding", schedule=schedule, remat=True,
        abstract=True)
    return step, cfg


@_restores_hcg
def run_proof(topology_name: str = "v4:2x4x4", mp: int = 8, pp: int = 4,
              sharding: int = 2, batch: int = 32, seq: int = 2048,
              n_micro: int = 8, budget_bytes: int = V4_HBM_PER_CORE,
              schedule: str = "1f1b") -> dict:
    import numpy as np
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=topology_name)
    n_dev = len(topo.devices)
    assert n_dev == mp * pp * sharding, (n_dev, mp, pp, sharding)

    step, cfg = build_step(mp, pp, sharding, n_micro, topo.devices,
                           schedule)

    # Physical axis assignment: the mesh solver must put mp (the
    # highest-bandwidth collectives) on the tightest ICI loops. Record
    # per-axis torus hop stats for the solved mesh vs the naive
    # enumeration-order reshape it replaces, and assert the solve wins.
    from paddle_tpu.distributed.topology import (get_hybrid_communicate_group,
                                                 mesh_axis_locality)
    import numpy as _np
    hcg = get_hybrid_communicate_group()
    axes = list(hcg.mesh.axis_names)
    solved = mesh_axis_locality(hcg.mesh.devices, axes)
    naive = mesh_axis_locality(
        _np.asarray(list(topo.devices)).reshape(hcg.mesh.devices.shape),
        axes)
    mesh_assignment = {
        "strategy": hcg.mesh_assignment,
        "solved_axis_hops": solved,
        "naive_reshape_axis_hops": naive,
    }
    if solved:
        assert solved["mp"]["mean_hop"] <= naive["mp"]["mean_hop"], (
            solved, naive)
        assert solved["mp"]["max_hop"] <= 1, (
            "mp axis must ride adjacent ICI links", solved)
    n_params = sum(
        int(np.prod(v.shape))
        for v in {**step.stacked, **step.shared}.values())

    t0 = time.time()
    lowered = step.lower(batch, seq)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    arg_b, out_b, temp_b, alias_b, code_b, live = _mem_bytes(compiled)

    # The chosen shardings ARE the input placements (GSPMD honors them):
    # record the per-group PartitionSpecs that were assigned.
    shardings = {
        "stacked_blocks": {
            suf: str(v.sharding.spec)
            for suf, v in sorted(step.stacked.items())},
        "shared": {n: str(v.sharding.spec)
                   for n, v in sorted(step.shared.items())},
        "batch": str(step._batch_pspec()),
        "zero_slots": "stacked moment slots +sharding axis "
                      "(first divisible free dim)",
    }

    report = {
        "topology": topology_name,
        "n_devices": n_dev,
        "degrees": {"mp": mp, "pp": pp, "sharding": sharding},
        "schedule": schedule,
        "model": {"params_b": round(n_params / 1e9, 3),
                  "hidden": cfg.hidden_size, "layers": cfg.num_layers,
                  "heads": cfg.num_heads, "vocab": cfg.vocab_size,
                  "dtype": cfg.dtype,
                  "loss_chunk_size": cfg.loss_chunk_size,
                  "remat": True},
        "batch": {"global_batch": batch, "seq_len": seq,
                  "n_micro": n_micro},
        "compile": {"lower_s": round(t_lower, 1),
                    "compile_s": round(t_compile, 1)},
        "per_device_bytes": {
            "arguments": arg_b, "outputs": out_b, "temps": temp_b,
            "aliased": alias_b, "generated_code": code_b,
            "live_estimate": live},
        "per_device_gib": {
            "arguments": round(arg_b / GIB, 3),
            "temps": round(temp_b / GIB, 3),
            "live_estimate": round(live / GIB, 3)},
        "hbm_budget_bytes": budget_bytes,
        "hbm_budget_gib": round(budget_bytes / GIB, 2),
        "fits": bool(live <= budget_bytes),
        "note": "budget is the per-core share (32 GiB chip / 2 cores); "
                "a megacore deployment sees 2x this budget per device",
        "mesh_assignment": mesh_assignment,
        "shardings": shardings,
    }
    return report


@_restores_hcg
def run_longctx_proof(topology_name: str = "v4:2x4x4", mp: int = 2,
                      pp: int = 4, sep: int = 8, dp: int = 1,
                      seq: int = 32768, n_micro: int = 2,
                      budget_bytes: int = V4_HBM_PER_CORE) -> dict:
    """Long-context at scale: the 10B model with ring-flash sequence
    parallelism (sep) composed with mp x pp x dp in ONE v4-64 mesh,
    S=32k, AOT-compiled with per-core HBM fit asserted. Ring hops run
    the Pallas flash kernel (force_flash_for_aot: the compile host is
    CPU but the target is TPU) with the O(S_local) custom-vjp backward."""
    import numpy as np
    from jax.experimental import topologies

    import paddle_tpu.optimizer as optim
    from paddle_tpu.distributed.topology import (
        HybridCommunicateGroup, set_hybrid_communicate_group)
    from paddle_tpu.models.gpt import ernie_10b
    from paddle_tpu.models.gpt_pipeline import GPTPipelineTrainStep

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=topology_name)
    n_dev = len(topo.devices)
    assert n_dev == mp * pp * sep * dp, (n_dev, mp, pp, sep, dp)
    hcg = HybridCommunicateGroup(
        mp_degree=mp, pp_degree=pp, sep_degree=sep, dp_degree=dp,
        devices=topo.devices, topology_aware=True)
    set_hybrid_communicate_group(hcg)
    cfg = ernie_10b(dropout=0.0, attn_dropout=0.0, dtype="bfloat16",
                    loss_chunk_size=512, seq_parallel_mode="zigzag")
    cfg.max_seq_len = seq
    step = GPTPipelineTrainStep(
        cfg, optim.AdamW(learning_rate=1e-4), pp=pp, n_micro=n_micro,
        hcg=hcg, zero_axis="sep", schedule="1f1b", remat=True,
        abstract=True)

    # the bf16 deployment recipe (bench_all's recipe + bf16 Adam slots):
    # abstract mode makes the cast a ShapeDtypeStruct remap
    import jax
    import jax.numpy as jnp

    from bench_all import BF16_KEEP_TOKENS

    def bf16_struct(name, v):
        if any(t in name for t in BF16_KEEP_TOKENS) or \
                v.dtype != jnp.float32:
            return v
        return jax.ShapeDtypeStruct(v.shape, jnp.bfloat16,
                                    sharding=v.sharding)

    step.stacked = {kk: bf16_struct(kk, vv)
                    for kk, vv in step.stacked.items()}
    step.shared = {kk: bf16_struct(kk, vv)
                   for kk, vv in step.shared.items()}
    step.opt_state = step._abstract_opt_init(
        {"stacked": step.stacked, "shared": step.shared})
    step._zero_shard_slots("sep")  # re-derivation reset the ZeRO specs
    batch = dp * n_micro
    t0 = time.time()
    from paddle_tpu.ops.pallas.flash_attention import force_flash_for_aot
    with force_flash_for_aot():  # target is TPU, host is CPU
        compiled = step.lower(batch, seq).compile()
    t_compile = time.time() - t0
    arg_b, out_b, temp_b, alias_b, code_b, live = _mem_bytes(compiled)
    n_params = sum(
        int(np.prod(v.shape))
        for v in {**step.stacked, **step.shared}.values())
    from paddle_tpu.distributed.topology import mesh_axis_locality
    return {
        "topology": topology_name, "n_devices": n_dev,
        "degrees": {"mp": mp, "pp": pp, "sep": sep, "dp": dp},
        "mesh_assignment": {
            "strategy": hcg.mesh_assignment,
            "solved_axis_hops": mesh_axis_locality(
                hcg.mesh.devices, list(hcg.mesh.axis_names))},
        "model": {"params_b": round(n_params / 1e9, 3),
                  "seq_len": seq, "seq_parallel": "zigzag ring (balanced causal "
                                  "schedule, flash hops)",
                  "precision": "bf16 params + bf16 Adam slots, fp32 "
                               "norms (the bench deployment recipe)",
                  "remat": True,
                  "loss_chunk_size": cfg.loss_chunk_size},
        "batch": {"global_batch": batch, "n_micro": n_micro,
                  "tokens_per_step": batch * seq},
        "compile_s": round(t_compile, 1),
        "per_device_gib": {"arguments": round(arg_b / GIB, 3),
                           "temps": round(temp_b / GIB, 3),
                           "live_estimate": round(live / GIB, 3)},
        "hbm_budget_gib": round(budget_bytes / GIB, 2),
        "fits": bool(live <= budget_bytes),
    }


def main():
    # Compile-only: keep this process on the host platform so lowering
    # sees backend=cpu and the flash auto-detect stays off outside the
    # scoped force_flash_for_aot (and no attached chip is claimed).
    import jax
    jax.config.update("jax_platforms", "cpu")
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="SCALE_PROOF.json")
    ap.add_argument("--topology", default="v4:2x4x4")
    ap.add_argument("--mp", type=int, default=8)
    ap.add_argument("--pp", type=int, default=4)
    ap.add_argument("--sharding", type=int, default=2)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--n-micro", type=int, default=8)
    ap.add_argument("--schedule", default="1f1b")
    ap.add_argument("--longctx", action="store_true",
                    help="run the S=32k ring-flash sep x mp x pp proof "
                         "instead")
    args = ap.parse_args()

    if args.longctx:
        if args.out == "SCALE_PROOF.json":  # don't clobber the base proof
            args.out = "SCALE_PROOF_LONGCTX.json"
        report = run_longctx_proof(args.topology)
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
            f.write("\n")
        print(json.dumps(report, indent=2))
        assert report["fits"], report["per_device_gib"]
        return

    report = run_proof(args.topology, args.mp, args.pp, args.sharding,
                       args.batch, args.seq, args.n_micro,
                       schedule=args.schedule)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(json.dumps(report, indent=2))
    assert report["fits"], (
        f"10B config does NOT fit: {report['per_device_gib']}")


if __name__ == "__main__":
    main()
