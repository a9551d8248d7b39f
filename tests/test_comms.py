"""Communication accounting: HLO collective parsing + wire-byte laws."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from byzpy_tpu.parallel.comms import (
    CollectiveOp,
    collective_traffic,
    collectives_in_hlo,
    compression_factor,
    scaling_model,
)


def test_parse_sync_and_async_collectives():
    hlo = """
HloModule m

ENTRY %main (p: f32[8,128]) -> f32[8,128] {
  %p = f32[8,128] parameter(0)
  %ar = f32[8,128]{1,0} all-reduce(%p), replica_groups={{0,1,2,3,4,5,6,7}}, to_apply=%add
  %ags = f32[8,128]{1,0} all-gather-start(%ar), replica_groups=[1,8]<=[8], dimensions={0}
  %agd = f32[8,128]{1,0} all-gather-done(%ags)
  ROOT %out = f32[8,128]{1,0} add(%ar, %agd)
}
"""
    ops = collectives_in_hlo(hlo, default_group=8)
    kinds = sorted(op.opcode for op in ops)
    # the -done twin must NOT double count
    assert kinds == ["all-gather", "all-reduce"], ops
    by = {op.opcode: op for op in ops}
    assert by["all-reduce"].group_size == 8
    assert by["all-gather"].group_size == 8
    assert by["all-reduce"].result_bytes == 8 * 128 * 4
    assert all(op.in_entry for op in ops)


def test_loop_body_collectives_flagged_not_totalled():
    hlo = """
HloModule m

%body (x: f32[64]) -> f32[64] {
  %x = f32[64] parameter(0)
  ROOT %cp = f32[64]{0} collective-permute(%x), source_target_pairs={{0,1},{1,0}}
}

ENTRY %main (p: f32[64]) -> f32[64] {
  %p = f32[64] parameter(0)
  ROOT %w = f32[64]{0} while(%p), condition=%cond, body=%body
}
"""
    ops = collectives_in_hlo(hlo, default_group=2)
    assert len(ops) == 1 and not ops[0].in_entry


def test_quantized_dtypes_counted_not_dropped():
    """Satellite of ISSUE 3: s8/u8/s16/u16/f8*/pred buffers must land in
    wire_bytes_per_device instead of silently vanishing from the traffic
    model — pinned with a hand-written int8 all-gather (the compressed
    fabric's dominant payload) plus fp8 and pred cousins."""
    hlo = """
HloModule m

ENTRY %main (p: s8[8,256]) -> s8[64,256] {
  %p = s8[8,256] parameter(0)
  %ag = s8[64,256]{1,0} all-gather(%p), replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
  %f8 = f8e4m3[8,256]{1,0} all-gather(%p2), replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
  %f8b = f8e5m2[8,256]{1,0} all-gather(%p3), replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
  %msk = pred[8,256]{1,0} all-gather(%p4), replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
  %s16 = s16[8,256]{1,0} all-gather(%p5), replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
  %u16 = u16[8,256]{1,0} all-gather(%p6), replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
  %u8 = u8[8,256]{1,0} all-gather(%p7), replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
  ROOT %out = s8[64,256]{1,0} copy(%ag)
}
"""
    ops = collectives_in_hlo(hlo, default_group=8)
    assert len(ops) == 7, ops
    by_bytes = {op.result_bytes for op in ops}
    # int8 gather result: 64*256*1 bytes; 1-byte cousins: 8*256; 2-byte: 8*256*2
    assert 64 * 256 in by_bytes
    assert 8 * 256 in by_bytes and 8 * 256 * 2 in by_bytes
    assert all(op.result_bytes > 0 for op in ops), "a dtype fell out of the table"
    int8_ag = next(op for op in ops if op.result_bytes == 64 * 256)
    assert int8_ag.wire_bytes_per_device == 64 * 256 * 7 // 8


def test_wire_byte_laws():
    assert CollectiveOp("all-gather", 1024, 8).wire_bytes_per_device == 1024 * 7 // 8
    assert CollectiveOp("all-reduce", 1024, 8).wire_bytes_per_device == 2 * 1024 * 7 // 8
    assert CollectiveOp("reduce-scatter", 128, 8).wire_bytes_per_device == 128 * 7
    assert CollectiveOp("all-to-all", 1024, 8).wire_bytes_per_device == 1024 * 7 // 8
    assert CollectiveOp("collective-permute", 1024, 8).wire_bytes_per_device == 1024
    # degenerate single-device group moves nothing (permute excepted)
    assert CollectiveOp("all-reduce", 1024, 1).wire_bytes_per_device == 0


def test_collective_traffic_measures_gradient_transpose(devices):
    mesh = Mesh(np.array(devices[:8]), ("nodes",))
    d = 4096

    @jax.jit
    def step(x):
        x = jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P("nodes", None)))
        y = jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(None, "nodes")))
        return jnp.sum(y, axis=0)

    x = jnp.ones((8, d), jnp.float32)
    traffic = collective_traffic(step, x)
    # node->feature transpose must appear as an all-to-all moving ~(g-1)/g
    # of the (8, d) f32 matrix's per-device share
    assert traffic["per_opcode_bytes"].get("all-to-all", 0) > 0, traffic
    assert traffic["wire_bytes_per_device"] > 0


def test_scaling_model_efficiency_saturates():
    pts = scaling_model(
        flops_per_chip=1e9,
        wire_bytes_fn=lambda g: 2.0 * 1e6 * 4 * (g - 1) / g,
        chips=(8, 128),
    )
    # comm is ~constant in N: 128-chip efficiency within 3% of 8-chip
    assert abs(pts[0].efficiency - pts[1].efficiency) < 0.03
    assert 0.0 < pts[0].efficiency < 1.0


def test_scaling_model_predicts_compressed_fabrics():
    """The comm term scales by the compression factor: int8 at block 256
    moves (1 + 4/256)/4 of the f32 bytes, bf16 exactly half."""
    kwargs = dict(
        flops_per_chip=1e9,
        wire_bytes_fn=lambda g: 8e6 * (g - 1) / g,
        chips=(8,),
    )
    full = scaling_model(**kwargs)[0]
    i8 = scaling_model(precision="int8", quant_block=256, **kwargs)[0]
    bf = scaling_model(precision="bf16", **kwargs)[0]
    assert i8.comm_s == pytest.approx(full.comm_s * (1 + 4 / 256) / 4)
    assert bf.comm_s == pytest.approx(full.comm_s / 2)
    assert i8.efficiency > bf.efficiency > full.efficiency
    assert compression_factor("off") == 1.0
    with pytest.raises(ValueError):
        compression_factor("fp4")


def test_loop_body_collectives_reported_separately(devices):
    """ring_all_reduce_sum runs its collective-permutes inside fori_loop
    bodies; the accounting must flag them as per-iteration lower bounds
    instead of silently under-counting the per-invocation total."""
    from byzpy_tpu.parallel.collectives import ring_all_reduce_sum, sharded_fn

    mesh = Mesh(np.array(devices[:8]), ("r",))
    fn = sharded_fn(
        mesh, "r", lambda s: ring_all_reduce_sum(s, "r"),
        in_spec=P("r"), out_spec=P("r"),
    )
    x = jnp.ones((8, 256), jnp.float32)
    traffic = collective_traffic(fn, x)
    assert traffic["loop_body_bytes_per_iteration"] > 0, traffic


def test_tpu_layouts_and_tuple_shapes_are_parsed():
    """Lines recorded from the fused PS step compiled for four v5e chips
    (PR 21, ``node_mesh(4)``, MNIST MLP, Multi-Krum): TPU layouts carry
    parentheses (``T(1024)S(1)``), which hid every tuple-shaped collective
    from the parser — here the d-sized all-reduce XLA:TPU lowers the params
    gather to (dynamic-update-slice into zeros, then all-reduce, combined
    with the two scalar psums)."""
    hlo = """
HloModule jit_train_step

ENTRY %main.17_spmd (param.7: f32[128]) -> f32[128] {
  %all-to-all = f32[2,4,25443]{2,0,1:T(2,128)S(1)} all-to-all(%copy.11), channel_id=1, replica_groups=[1,4]<=[4], dimensions={1}, metadata={op_name="jit(train_step)/slice" stack_frame_id=30}
  %bitcast.49 = f32[4,2,25443]{2,1,0:T(2,128)S(1)} bitcast(%all-to-all)
  %all-reduce = f32[8,8]{1,0:T(8,128)S(1)} all-reduce(%fusion.684), channel_id=2, replica_groups=[1,4]<=[4], use_global_device_ids=true, to_apply=%add.clone
  %all-reduce.3 = (f32[101772]{0:T(1024)S(1)}, f32[]{:T(128)}, f32[]{:T(128)}) all-reduce(%get-tuple-element.41, %get-tuple-element.46, %fusion.38), channel_id=2, replica_groups=[1,4]<=[4], use_global_device_ids=true, to_apply=%add
  %get-tuple-element.38 = f32[101772]{0:T(1024)S(1)} get-tuple-element(%all-reduce.3), index=0
}
"""
    ops = collectives_in_hlo(hlo, default_group=4)
    assert [(op.opcode, op.result_bytes, op.group_size) for op in ops] == [
        ("all-to-all", 2 * 4 * 25443 * 4, 4),
        ("all-reduce", 8 * 8 * 4, 4),
        ("all-reduce", 101772 * 4 + 4 + 4, 4),
    ]
    assert all(op.in_entry for op in ops)
