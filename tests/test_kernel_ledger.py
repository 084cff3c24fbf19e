"""Kernel roofline ledger (obs/kernels.py): profiler trace × HLO cost
model → kernels.json → report.

The acceptance loop on the CPU rig: a traced run's per-kernel FLOPs sum
to the ledger-MFU numerator (XLA's cost-analysis total over the shared
``PEAK_FLOPS`` denominator), ``kernels.json`` is written by a traced
driver run, and ``python -m scalable_agent_tpu.obs.report --json``
names the dominant kernel — plus the report's bench-artifact section
naming ``conv0_gradw`` from the committed r04/r05 readings
automatically.
"""

import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scalable_agent_tpu.obs import kernels as kernels_lib

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _compiled_conv_dot():
    def f(x, w, m):
        y = jax.lax.conv_general_dilated(
            x, w, (2, 2), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return (jnp.tanh(y).reshape(x.shape[0], -1)[:, :64] @ m).sum()

    x = jnp.ones((8, 32, 32, 3))
    w = jnp.ones((5, 5, 3, 16))
    m = jnp.ones((64, 32))
    compiled = jax.jit(f).lower(x, w, m).compile()
    return compiled, (x, w, m)


class TestHloCostModel:
    def test_dot_flops_exact(self):
        hlo = """
ENTRY %main (a: f32[128,64], b: f32[64,32]) -> f32[128,32] {
  %a = f32[128,64]{1,0} parameter(0)
  %b = f32[64,32]{1,0} parameter(1)
  ROOT %dot.1 = f32[128,32]{1,0} dot(f32[128,64]{1,0} %a, f32[64,32]{1,0} %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""
        costs = kernels_lib.parse_hlo_kernel_costs(hlo)
        assert costs["dot.1"]["flops_est"] == 2 * 128 * 32 * 64
        # bytes: both operands + the result, f32.
        assert costs["dot.1"]["bytes"] == 4 * (128 * 64 + 64 * 32
                                               + 128 * 32)
        assert costs["a"]["flops_est"] == 0.0  # parameters are free

    def test_fusion_sums_called_computation(self):
        hlo = """
%fused (p: f32[1024]) -> f32[1024] {
  %p = f32[1024]{0} parameter(0)
  %t = f32[1024]{0} tanh(f32[1024]{0} %p)
  ROOT %m = f32[1024]{0} multiply(f32[1024]{0} %t, f32[1024]{0} %t)
}
ENTRY %main (x: f32[1024]) -> f32[1024] {
  %x = f32[1024]{0} parameter(0)
  ROOT %my_fusion = f32[1024]{0} fusion(f32[1024]{0} %x), kind=kLoop, calls=%fused
}
"""
        costs = kernels_lib.parse_hlo_kernel_costs(hlo)
        assert costs["my_fusion"]["flops_est"] == 2 * 1024
        # Fusion bytes are the kernel-boundary traffic, not the
        # internal temporaries.
        assert costs["my_fusion"]["bytes"] == 4 * 2 * 1024

    def test_scope_attribution_from_op_name_metadata(self):
        """ISSUE 15: jax.named_scope markers (runtime/ingraph.py wraps
        env_step / actor_inference / learner_update) surface through
        the HLO op_name metadata as a per-instruction ``scope`` and an
        aggregate ``scope_time_shares`` — the env-vs-learner split the
        report names inside a device_bound verdict."""
        hlo = """
ENTRY %main (a: f32[128,64], b: f32[64,32]) -> f32[128,32] {
  %a = f32[128,64]{1,0} parameter(0)
  %b = f32[64,32]{1,0} parameter(1)
  %env.1 = f32[128,64]{1,0} tanh(f32[128,64]{1,0} %a), metadata={op_name="jit(_fused)/while/body/env_step/tanh"}
  %infer.1 = f32[128,64]{1,0} negate(f32[128,64]{1,0} %env.1), metadata={op_name="jit(_fused)/while/body/actor_inference/neg"}
  %upd.1 = f32[64,32]{1,0} exponential(f32[64,32]{1,0} %b), metadata={op_name="jit(_fused)/learner_update/exp"}
  ROOT %dot.1 = f32[128,32]{1,0} dot(f32[128,64]{1,0} %infer.1, f32[64,32]{1,0} %upd.1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""
        costs = kernels_lib.parse_hlo_kernel_costs(hlo)
        assert costs["env.1"]["scope"] == "env"
        assert costs["infer.1"]["scope"] == "inference"
        assert costs["upd.1"]["scope"] == "learner"
        assert costs["dot.1"]["scope"] is None

        events = {
            "env.1": {"time_us": 30.0, "calls": 1.0},
            "infer.1": {"time_us": 20.0, "calls": 1.0},
            "upd.1": {"time_us": 40.0, "calls": 1.0},
            "dot.1": {"time_us": 10.0, "calls": 1.0},
        }
        table = kernels_lib.build_kernel_table(events, costs,
                                               peak_flops=1e12)
        shares = table["scope_time_shares"]
        assert shares["env"] == pytest.approx(0.30)
        assert shares["inference"] == pytest.approx(0.20)
        assert shares["learner"] == pytest.approx(0.40)
        assert shares["unattributed"] == pytest.approx(0.10)
        by_name = {row["name"]: row for row in table["kernels"]}
        assert by_name["env.1"]["scope"] == "env"

    def test_pallas_gradw_custom_call_flops(self):
        """ISSUE 18: a pallas_call lowers to a custom-call XLA cannot
        see inside, so the named grad-W kernel gets an explicit cost —
        2 * rows * prod(g), what its MXU executes, off the operand and
        result shapes — instead of the one-flop-per-element floor
        (which would misprice the MXU matmul by ~3 orders of magnitude
        and hide it from the worst-kernel verdict)."""
        hlo = """
ENTRY %main (xt: bf16[76,3,112,256], gt: bf16[18,24,32,256]) -> f32[768,128] {
  %xt = bf16[76,3,112,256]{3,2,1,0} parameter(0)
  %gt = bf16[18,24,32,256]{3,2,1,0} parameter(1)
  ROOT %cc.1 = f32[768,128]{1,0} custom-call(bf16[76,3,112,256]{3,2,1,0} %xt, bf16[18,24,32,256]{3,2,1,0} %gt), custom_call_target="tpu_custom_call", metadata={op_name="jit(update)/pallas_conv0_gradw/pallas_call"}
}
"""
        costs = kernels_lib.parse_hlo_kernel_costs(hlo)
        # The g operand is the 4-d input whose third dim, F, divides
        # the band's columns (JG*F); each of its N*OH*OW/JG groups is
        # one [768, N-tile] x [JG*F, N-tile]^T matmul.
        assert costs["cc.1"]["flops_est"] == pytest.approx(
            2 * 768 * 128 * (256 * 18 * 24 // 4))
        assert costs["cc.1"]["op"] == "custom-call"

    def test_unrecognized_custom_call_keeps_elementwise_floor(self):
        """A custom-call without a registered Pallas cost entry must
        stay on the explicit one-flop-per-element floor, not crash or
        inherit another kernel's formula."""
        hlo = """
ENTRY %main (a: f32[64,32]) -> f32[64,32] {
  %a = f32[64,32]{1,0} parameter(0)
  ROOT %cc.9 = f32[64,32]{1,0} custom-call(f32[64,32]{1,0} %a), custom_call_target="tpu_custom_call", metadata={op_name="jit(update)/some_other_kernel/pallas_call"}
}
"""
        costs = kernels_lib.parse_hlo_kernel_costs(hlo)
        assert costs["cc.9"]["flops_est"] == 64 * 32

    def test_gradw_marker_matches_ops_contract(self):
        """The cost-model marker string and ops/conv_pallas.py's
        GRADW_KERNEL_NAME are the same contract — kernels.py is
        jax-free so it cannot import the op; this pins the two sides
        together."""
        from scalable_agent_tpu.ops import conv_pallas

        assert (kernels_lib._PALLAS_GRADW_MARKER
                == conv_pallas.GRADW_KERNEL_NAME)

    def test_real_compiled_module_parses_and_names_ops(self):
        compiled, _ = _compiled_conv_dot()
        costs = kernels_lib.parse_hlo_kernel_costs(compiled.as_text())
        conv = [n for n, c in costs.items() if c["op"] == "convolution"]
        dots = [n for n, c in costs.items() if c["op"] == "dot"]
        assert conv and dots
        # Conv flops: 2 * out_elems * kernel_taps_per_output.
        (conv_name, ) = conv
        assert costs[conv_name]["flops_est"] == pytest.approx(
            2 * (8 * 16 * 16 * 16) * (5 * 5 * 3))


_PARTITIONED_MODULE = """
HloModule jit__fused

%fused_gather.1 (param_0: f32[100,256]) -> f32[100,1024] {
  %param_0 = f32[100,256]{1,0} parameter(0)
  ROOT %all-gather.7 = f32[100,1024]{1,0} all-gather(%param_0), channel_id=3, replica_groups=[1,4]<=[4], dimensions={1}, metadata={op_name="jit(_fused)/telemetry/jit(quantile)/sort"}
}

%fused_gather.2 (param_0: f32[100,256]) -> f32[100,1024] {
  %param_0 = f32[100,256]{1,0} parameter(0)
  ROOT %all-gather.9 = f32[100,1024]{1,0} all-gather(%param_0), channel_id=3, replica_groups=[1,4]<=[4], dimensions={1}
}

ENTRY %main (p0: u8[101,256,72,96,3], p1: f32[256,9]) -> f32[256,9] {
  %p0 = u8[101,256,72,96,3]{4,3,2,1,0} parameter(0)
  %p1 = f32[256,9]{1,0} parameter(1)
  %all-gather-start.1 = (u8[101,256,72,96,3]{4,3,2,1,0}, u8[101,1024,72,96,3]{4,3,2,1,0}) all-gather-start(%p0), channel_id=1, dimensions={1}, metadata={op_name="jit(_fused)/learner_update/jvp(ImpalaAgent)/reshape"}
  %all-gather-done.1 = u8[101,1024,72,96,3]{4,3,2,1,0} all-gather-done(%all-gather-start.1)
  %all-reduce.5 = (f32[256,9]{1,0}, f32[]) all-reduce(%p1, %c), channel_id=2, to_apply=%add
  %collective-permute-start.2 = (f32[8,128]{1,0}, f32[8,128]{1,0}, u32[], u32[]) collective-permute-start(%x), channel_id=4, source_target_pairs={{0,1}}
  ROOT %r = f32[256,9]{1,0} get-tuple-element(%all-reduce.5), index=0
}
"""


class TestCollectives:
    """What the partitioner made a step move between devices (ISSUE
    26), read off compiled text: per-device result dims and bytes."""

    def test_rows_and_bytes_by_kind(self):
        rows = {row["name"]: row
                for row in kernels_lib.collectives(_PARTITIONED_MODULE)}
        # one transfer chained over two fused computations counts once
        assert sorted(rows) == ["all-gather-start.1", "all-gather.7",
                                "all-reduce.5",
                                "collective-permute-start.2"]
        frames = rows["all-gather-start.1"]
        # the async pair's result lists the operand first: dropped
        assert frames["dims"] == [[101, 1024, 72, 96, 3]]
        assert frames["bytes"] == 101 * 1024 * 72 * 96 * 3
        assert frames["kind"] == "all_gather"
        assert frames["op_name"].endswith("jvp(ImpalaAgent)/reshape")
        assert rows["all-reduce.5"]["bytes"] == 4 * (256 * 9 + 1)
        assert rows["collective-permute-start.2"]["dims"] == [[8, 128]]
        assert kernels_lib.collective_bytes(rows.values()) == {
            "all_gather": frames["bytes"] + 4 * 100 * 1024,
            "all_reduce": 4 * (256 * 9 + 1),
            "other": 4 * 8 * 128,
        }

    def test_scope_table_carries_them_and_sets_the_gauges(self, tmp_path):
        import json

        from scalable_agent_tpu.obs import MetricsRegistry

        registry = MetricsRegistry()
        path = kernels_lib.write_op_scopes(
            str(tmp_path / "trace.p0.7.json"), _PARTITIONED_MODULE,
            registry=registry)
        table = json.load(open(path))
        assert table["ops"]["all-gather.7"].endswith("jit(quantile)/sort")
        notes = table["notes"]
        assert notes["largest_collectives"][0]["name"] == (
            "all-gather-start.1")
        gauges = registry.snapshot()
        for kind, value in notes["collective_bytes"].items():
            assert gauges[f"spmd/collective_bytes/{kind}"] == value > 0

    def test_a_one_device_module_moves_nothing(self):
        text = "ENTRY %main () -> f32[] {\n  ROOT %c = f32[] constant(0)\n}"
        assert kernels_lib.collectives(text) == []
        assert set(kernels_lib.collective_bytes([]).values()) == {0}


# Condensed from the compiled step of ``deep.ingraph`` (benchmark/aot.py
# for a v5e, ISSUE 27): the ResNet's stem conv as the forward computes
# it, and again in the backward under the stem segment's checkpoint;
# the pool's backward sits inside the boundary and is not recomputed.
_REMAT_UPDATE = ("jit(_fused)/while/body/closed_call/learner_update/"
                 "transpose(jvp(ImpalaAgent))/convnet/learner_update/"
                 "jvp(ImpalaAgent)/convnet/checkpoint")
_REMAT_MODULE = """
HloModule jit__fused

%fused_computation.232 (param_0: bf16[12928,72,96,3], param_1: bf16[3,3,3,16]) -> bf16[12928,72,96,16] {
  %param_0 = bf16[12928,72,96,3]{0,3,2,1} parameter(0)
  %param_1 = bf16[3,3,3,16]{3,2,1,0} parameter(1)
  ROOT %conv_general_dilated.289 = bf16[12928,72,96,16]{0,3,2,1} convolution(%param_0, %param_1), window={size=3x3 pad=1_1x1_1}, dim_labels=b01f_01io->b01f, metadata={op_name="jit(_fused)/while/body/closed_call/learner_update/jvp(ImpalaAgent)/convnet/convnet._stem/downscale_0/conv_general_dilated" stack_frame_id=4}
}

%fused_computation.214 (param_0: u8[12928,72,96,3], param_1: bf16[3,3,3,16]) -> bf16[12928,72,96,16] {
  %param_0 = u8[12928,72,96,3]{0,2,3,1} parameter(0)
  %convert_multiply_fusion.2 = bf16[12928,72,96,3]{0,3,2,1} fusion(%param_0), kind=kLoop, calls=%fused_computation.234, metadata={op_name="REMAT/rematted_computation/convnet._stem/div" stack_frame_id=486}
  %param_1 = bf16[3,3,3,16]{3,2,1,0} parameter(1)
  ROOT %conv_general_dilated.287 = bf16[12928,72,96,16]{0,3,2,1} convolution(%convert_multiply_fusion.2, %param_1), window={size=3x3 pad=1_1x1_1}, dim_labels=b01f_01io->b01f, metadata={op_name="REMAT/rematted_computation/convnet._stem/downscale_0/conv_general_dilated" stack_frame_id=486}
}

ENTRY %main (p0: u8[12928,72,96,3], p1: bf16[3,3,3,16], p2: bf16[12928,36,48,16]) -> bf16[12928,72,96,16] {
  %p0 = u8[12928,72,96,3]{0,2,3,1} parameter(0)
  %p1 = bf16[3,3,3,16]{3,2,1,0} parameter(1)
  %p2 = bf16[12928,36,48,16]{0,3,2,1} parameter(2)
  %fusion.173 = bf16[12928,72,96,3]{0,3,2,1} fusion(%p0), kind=kLoop, calls=%fused_computation.235, metadata={op_name="jit(_fused)/while/body/closed_call/learner_update/jvp(ImpalaAgent)/convnet/convnet._stem/div"}
  %fusion.172 = bf16[12928,72,96,16]{0,3,2,1} fusion(%fusion.173, %p1), kind=kOutput, calls=%fused_computation.232, metadata={op_name="jit(_fused)/while/body/closed_call/learner_update/jvp(ImpalaAgent)/convnet/convnet._stem/downscale_0/conv_general_dilated" stack_frame_id=4}
  %convert_element_type.119 = bf16[3,3,3,16]{3,2,1,0} convert(%p1), metadata={op_name="REMAT/rematted_computation/convnet._stem/downscale_0/convert_element_type" stack_frame_id=486}
  %convolution_add_fusion.1 = bf16[12928,72,96,16]{0,3,2,1} fusion(%p0, %convert_element_type.119), kind=kOutput, calls=%fused_computation.214, metadata={op_name="REMAT/rematted_computation/convnet._stem/downscale_0/conv_general_dilated" stack_frame_id=486}
  ROOT %select-and-scatter.5 = bf16[12928,72,96,16]{0,3,2,1} select-and-scatter(%convolution_add_fusion.1, %p2, %c), window={size=3x3 stride=2x2 pad=0_1x0_1}, select=%ge, scatter=%add, metadata={op_name="REMAT/convnet._stem/select_and_scatter_add" stack_frame_id=486}
}
""".replace("REMAT", _REMAT_UPDATE)


class TestRematerialized:
    """What a step recomputes under ``jax.checkpoint`` (ISSUE 27), read
    off compiled text by the ``checkpoint`` / ``rematted_computation``
    components of an instruction's ``op_name`` path."""

    def test_counts_and_names_the_recomputed_instructions(self):
        found = kernels_lib.rematerialized(_REMAT_MODULE)
        # the forward's own conv (fusion.172) is computed once: not named
        assert list(found["names"]) == ["convolution_add_fusion.1",
                                        "convert_element_type.119"]
        assert found["names"]["convolution_add_fusion.1"].endswith(
            "rematted_computation/convnet._stem/downscale_0/"
            "conv_general_dilated")
        # instructions a trace names; a fusion's body is not among them
        assert found["instructions"] == 2
        assert found["convolutions"] == 1
        # + the two inside the recomputed fusion's body and the pool's
        # backward, which is inside the boundary and computed once
        assert found["checkpointed_instructions"] == 5

    def test_a_step_with_no_checkpoint_recomputes_nothing(self):
        assert kernels_lib.rematerialized(_PARTITIONED_MODULE) == {
            "instructions": 0, "convolutions": 0, "names": {},
            "checkpointed_instructions": 0}

    def test_scope_table_notes_carry_it(self, tmp_path):
        import json

        from scalable_agent_tpu.obs import MetricsRegistry

        path = kernels_lib.write_op_scopes(
            str(tmp_path / "trace.p0.7.json"), _REMAT_MODULE,
            registry=MetricsRegistry())
        notes = json.load(open(path))["notes"]
        assert notes["rematerialized"] == kernels_lib.rematerialized(
            _REMAT_MODULE)
        assert notes["rematerialized"]["convolutions"] == 1


# Condensed from the compiled step of ``shallow.ingraph`` (benchmark/
# aot.py's way, for a v5e, ISSUE 29), layouts and tilings as printed.
# The parent: the scan stacks 100 frames time-major into an allocated
# buffer, ``_stack_first``'s concatenate (pad + add) makes the 101, a
# copy moves T inside [H][C][W/8] for the merge; the stem weight
# gradient's pad reads the merged frames inside its own fusion.
_FRAMES_SCOPE = "jit(_fused)/while/body/closed_call"
_FRAMES_PARENT = """
HloModule jit__fused

%fused_computation.152 (param_0.1589: u8[25856,72,96,3]) -> bf16[76,3,112,25856] {
  %param_0.1589 = u8[25856,72,96,3]{0,2,3,1:T(8,128)(4,1)} parameter(0)
  %constant.2319 = u8[]{:T(256)} constant(0)
  %pad.90 = u8[25856,76,112,3]{0,2,3,1:T(8,128)(4,1)} pad(%param_0.1589, %constant.2319), padding=0_0x2_2x2_14x0_0, metadata={op_name="SCOPE/learner_update/transpose(learner_update)/jvp(ImpalaAgent)/convnet/conv_0/jit(_pad)/pad"}
  %convert.1 = bf16[25856,76,112,3]{0,2,3,1:T(8,128)(2,1)} convert(%pad.90)
  ROOT %bitcast.3 = bf16[76,3,112,25856]{3,2,1,0:T(8,128)(2,1)} bitcast(%convert.1)
}

%fused_computation.163 (param_0.566: u8[100,256,72,96,3], param_1.1978: u8[1,256,72,96,3]) -> u8[101,72,3,12,8,256] {
  %param_1.1978 = u8[1,256,72,96,3]{1,3,4,2,0:T(8,128)(4,1)S(1)} parameter(1)
  %constant.2320 = u8[]{:T(256)} constant(0)
  %pad.92 = u8[101,256,72,96,3]{1,3,4,2,0:T(8,128)(4,1)} pad(%param_1.1978, %constant.2320), padding=0_100x0_0x0_0x0_0x0_0, metadata={op_name="SCOPE/concatenate"}
  %param_0.566 = u8[100,256,72,96,3]{1,3,4,2,0:T(8,128)(4,1)} parameter(0)
  %pad.91 = u8[101,256,72,96,3]{1,3,4,2,0:T(8,128)(4,1)} pad(%param_0.566, %constant.2320), padding=1_0x0_0x0_0x0_0x0_0, metadata={op_name="SCOPE/concatenate"}
  %add.2566 = u8[101,256,72,96,3]{1,3,4,2,0:T(8,128)(4,1)} add(%pad.92, %pad.91), metadata={op_name="SCOPE/concatenate"}
  ROOT %bitcast.101 = u8[101,72,3,12,8,256]{5,4,3,2,1,0:T(8,128)(4,1)} bitcast(%add.2566)
}

%fused_computation.36 (param_0.1981: u8[100,256,72,96,3], param_1.2341: s32[], param_2.2164: u8[256,72,96,3]) -> u8[100,256,72,96,3] {
  %param_0.1981 = u8[100,256,72,96,3]{1,3,4,2,0:T(8,128)(4,1)} parameter(0)
  %param_2.2164 = u8[256,72,96,3]{0,2,3,1:T(8,128)(4,1)S(1)} parameter(2)
  %bitcast.232 = u8[1,256,72,96,3]{1,3,4,2,0:T(8,128)(4,1)} bitcast(%param_2.2164)
  %param_1.2341 = s32[]{:T(128)} parameter(1)
  %constant.3017 = s32[]{:T(128)} constant(0)
  ROOT %dynamic_update_slice.149 = u8[100,256,72,96,3]{1,3,4,2,0:T(8,128)(4,1)} dynamic-update-slice(%param_0.1981, %bitcast.232, %param_1.2341, %constant.3017, %constant.3017, /*index=5*/%constant.3017, %constant.3017), metadata={op_name="SCOPE/rollout/while/body/dynamic_update_slice"}
}

%region_1.26 (arg_tuple.4: (s32[], u8[256,72,96,3], u8[100,256,72,96,3])) -> (s32[], u8[256,72,96,3], u8[100,256,72,96,3]) {
  %arg_tuple.4 = (s32[]{:T(128)}, u8[256,72,96,3]{0,2,3,1:T(8,128)(4,1)S(1)}, u8[100,256,72,96,3]{1,3,4,2,0:T(8,128)(4,1)}) parameter(0)
  %get-tuple-element.2959 = s32[]{:T(128)} get-tuple-element(%arg_tuple.4), index=0
  %get-tuple-element.2968 = u8[256,72,96,3]{0,2,3,1:T(8,128)(4,1)S(1)} get-tuple-element(%arg_tuple.4), index=1
  %get-tuple-element.2978 = u8[100,256,72,96,3]{1,3,4,2,0:T(8,128)(4,1)} get-tuple-element(%arg_tuple.4), index=2
  %bitcast_dynamic-update-slice_fusion.7 = u8[100,256,72,96,3]{1,3,4,2,0:T(8,128)(4,1)} fusion(%get-tuple-element.2978, %get-tuple-element.2959, %get-tuple-element.2968), kind=kLoop, calls=%fused_computation.36, metadata={op_name="SCOPE/rollout/while/body/dynamic_update_slice"}
  ROOT %tuple.392 = (s32[]{:T(128)}, u8[256,72,96,3]{0,2,3,1:T(8,128)(4,1)S(1)}, u8[100,256,72,96,3]{1,3,4,2,0:T(8,128)(4,1)}) tuple(%get-tuple-element.2959, %get-tuple-element.2968, %bitcast_dynamic-update-slice_fusion.7)
}

ENTRY %main.260 (frame.1: u8[256,72,96,3]) -> bf16[76,3,112,25856] {
  %frame.1 = u8[256,72,96,3]{0,2,3,1:T(8,128)(4,1)} parameter(0), metadata={op_name="carry.rollout.env_output.observation.frame"}
  %custom-call.14 = u8[100,256,72,96,3]{1,3,4,2,0:T(8,128)(4,1)} custom-call(), custom_call_target="AllocateBuffer", metadata={op_name="SCOPE/rollout/broadcast_in_dim"}
  %while.10 = u8[100,256,72,96,3]{1,3,4,2,0:T(8,128)(4,1)} get-tuple-element(%while.71), index=2
  %bitcast.255 = u8[1,256,72,96,3]{1,3,4,2,0:T(8,128)(4,1)S(1)} bitcast(%frame.1)
  %add_bitcast_fusion = u8[101,72,3,12,8,256]{5,4,3,2,1,0:T(8,128)(4,1)} fusion(%while.10, %bitcast.255), kind=kLoop, calls=%fused_computation.163
  %copy.78 = u8[101,72,3,12,8,256]{5,4,0,3,2,1:T(8,128)(4,1)} copy(%add_bitcast_fusion), metadata={op_name="SCOPE/learner_update/jvp(ImpalaAgent)/reshape"}
  %bitcast.62 = u8[25856,72,96,3]{0,2,3,1:T(8,128)(4,1)} bitcast(%copy.78), metadata={op_name="SCOPE/learner_update/jvp(ImpalaAgent)/reshape"}
  ROOT %multiply_bitcast_fusion = bf16[76,3,112,25856]{3,2,1,0:T(8,128)(2,1)} fusion(%bitcast.62), kind=kLoop, calls=%fused_computation.152
}
""".replace("SCOPE", _FRAMES_SCOPE)
# The change: the carry's buffer [H, C, W/8, T+1, 8, B] takes slot 0
# and, in the scan, slot t+1, both in place; the merge is a bitcast.
_FRAMES_CHANGE = """
HloModule jit__fused

%fused_computation.151 (param_0.1581: u8[25856,72,96,3]) -> bf16[76,3,112,25856] {
  %param_0.1581 = u8[25856,72,96,3]{0,2,3,1:T(8,128)(4,1)} parameter(0)
  %constant.2318 = u8[]{:T(256)} constant(0)
  %pad.88 = u8[25856,76,112,3]{0,2,3,1:T(8,128)(4,1)} pad(%param_0.1581, %constant.2318), padding=0_0x2_2x2_14x0_0
  %convert.1 = bf16[25856,76,112,3]{0,2,3,1:T(8,128)(2,1)} convert(%pad.88)
  ROOT %bitcast.3 = bf16[76,3,112,25856]{3,2,1,0:T(8,128)(2,1)} bitcast(%convert.1)
}

%fused_computation.36 (param_0.1975: u8[72,3,12,101,8,256], param_1.2336: s32[], param_2.2159: u8[256,72,96,3]) -> u8[72,3,12,101,8,256] {
  %param_0.1975 = u8[72,3,12,101,8,256]{5,4,3,2,1,0:T(8,128)(4,1)} parameter(0)
  %param_2.2159 = u8[256,72,96,3]{0,2,3,1:T(8,128)(4,1)S(1)} parameter(2)
  %bitcast.248 = u8[72,3,12,1,8,256]{5,4,3,2,1,0:T(8,128)(4,1)} bitcast(%param_2.2159)
  %constant.3024 = s32[]{:T(128)} constant(0)
  %param_1.2336 = s32[]{:T(128)} parameter(1)
  ROOT %dynamic_update_slice.152 = u8[72,3,12,101,8,256]{5,4,3,2,1,0:T(8,128)(4,1)} dynamic-update-slice(%param_0.1975, %bitcast.248, %constant.3024, %constant.3024, %constant.3024, /*index=5*/%param_1.2336, %constant.3024, %constant.3024), metadata={op_name="SCOPE/rollout/while/body/closed_call/dynamic_update_slice"}
}

%region_1.26 (arg_tuple.4: (s32[], u8[256,72,96,3], u8[72,3,12,101,8,256])) -> (s32[], u8[256,72,96,3], u8[72,3,12,101,8,256]) {
  %arg_tuple.4 = (s32[]{:T(128)}, u8[256,72,96,3]{0,2,3,1:T(8,128)(4,1)S(1)}, u8[72,3,12,101,8,256]{5,4,3,2,1,0:T(8,128)(4,1)}) parameter(0)
  %get-tuple-element.2960 = s32[]{:T(128)} get-tuple-element(%arg_tuple.4), index=0
  %get-tuple-element.2970 = u8[256,72,96,3]{0,2,3,1:T(8,128)(4,1)S(1)} get-tuple-element(%arg_tuple.4), index=1
  %get-tuple-element.2992 = u8[72,3,12,101,8,256]{5,4,3,2,1,0:T(8,128)(4,1)} get-tuple-element(%arg_tuple.4), index=2
  %bitcast_dynamic-update-slice_fusion.8 = u8[72,3,12,101,8,256]{5,4,3,2,1,0:T(8,128)(4,1)} fusion(%get-tuple-element.2992, %get-tuple-element.2960, %get-tuple-element.2970), kind=kLoop, calls=%fused_computation.36, metadata={op_name="SCOPE/rollout/while/body/closed_call/dynamic_update_slice"}
  ROOT %tuple.392 = (s32[]{:T(128)}, u8[256,72,96,3]{0,2,3,1:T(8,128)(4,1)S(1)}, u8[72,3,12,101,8,256]{5,4,3,2,1,0:T(8,128)(4,1)}) tuple(%get-tuple-element.2960, %get-tuple-element.2970, %bitcast_dynamic-update-slice_fusion.8)
}

ENTRY %main.260 (frame.1: u8[256,72,96,3], carry_frames.1: u8[72,3,12,101,8,256]) -> bf16[76,3,112,25856] {
  %frame.1 = u8[256,72,96,3]{0,2,3,1:T(8,128)(4,1)} parameter(0), metadata={op_name="carry.rollout.env_output.observation.frame"}
  %carry_frames.1 = u8[72,3,12,101,8,256]{5,4,3,2,1,0:T(8,128)(4,1)} parameter(1), metadata={op_name="carry.frames"}
  %constant.1 = s32[]{:T(128)} constant(0)
  %bitcast_dynamic-update-slice_fusion.2 = u8[72,3,12,101,8,256]{5,4,3,2,1,0:T(8,128)(4,1)} fusion(%carry_frames.1, %constant.1, %frame.1), kind=kLoop, calls=%fused_computation.36, metadata={op_name="SCOPE/rollout/dynamic_update_slice"}
  %get-tuple-element.3494 = u8[72,3,12,101,8,256]{5,4,3,2,1,0:T(8,128)(4,1)} get-tuple-element(%while.71), index=2
  %bitcast.10 = u8[25856,72,96,3]{0,2,3,1:T(8,128)(4,1)} bitcast(%get-tuple-element.3494), metadata={op_name="SCOPE/learner_update/jvp(ImpalaAgent)/reshape;reshape"}
  ROOT %multiply_bitcast_fusion = bf16[76,3,112,25856]{3,2,1,0:T(8,128)(2,1)} fusion(%bitcast.10), kind=kLoop, calls=%fused_computation.151
}
""".replace("SCOPE", _FRAMES_SCOPE)
_FRAME_TENSOR_BYTES = 101 * 256 * 72 * 96 * 3        # 536 MB


class TestFrameRelayouts:
    """What a compiled step spends writing the trajectory's whole uint8
    frame tensor out again (ISSUE 29), read off its text."""

    def test_the_parents_step_holds_the_frames_twice_more(self):
        rows = kernels_lib.frame_relayouts(_FRAMES_PARENT)
        # the concatenate (a pad-as-add fusion) and the transposing
        # copy; not the scan's in-place write of 100 slots, its
        # allocation, or the stem weight gradient's pad (inside its
        # fusion, and larger than the tensor)
        assert [(row["name"], row["op"]) for row in rows] == [
            ("add_bitcast_fusion", "fusion"), ("copy.78", "copy")]
        assert [row["bytes"] for row in rows] == [_FRAME_TENSOR_BYTES] * 2
        assert sum(row["bytes"] for row in rows) == 1_072_300_032
        assert rows[1]["dims"] == [101, 72, 3, 12, 8, 256]
        assert rows[1]["op_name"].endswith("jvp(ImpalaAgent)/reshape")

    def test_written_once_there_is_nothing_to_count(self):
        # both in-place slot writes result in the whole tensor: fusions
        # around a dynamic-update-slice, so neither is a row
        assert kernels_lib.frame_relayouts(_FRAMES_CHANGE) == []

    def test_a_module_with_no_uint8_array_has_no_frames(self):
        assert kernels_lib.frame_relayouts(_PARTITIONED_MODULE) == []

    @pytest.mark.parametrize("text,expected", [
        (_FRAMES_PARENT, 2 * _FRAME_TENSOR_BYTES), (_FRAMES_CHANGE, 0)],
        ids=["parent", "change"])
    def test_scope_table_notes_and_gauge_carry_it(self, tmp_path, text,
                                                  expected):
        import json

        from scalable_agent_tpu.obs import MetricsRegistry

        registry = MetricsRegistry()
        path = kernels_lib.write_op_scopes(
            str(tmp_path / "trace.p0.7.json"), text, registry=registry)
        notes = json.load(open(path))["notes"]
        assert notes["frame_relayout_bytes"] == expected
        assert notes["frame_relayouts"] == kernels_lib.frame_relayouts(
            text)
        assert registry.snapshot()["fused/frame_relayout_bytes"] == (
            expected)
        # beside it, the hand-over's share: nothing traced a step into
        # this registry
        assert notes["stem_handed_share"] == 0.0

    @pytest.mark.parametrize("conv_backend,share", [
        ("pallas", 5 / 6), ("xla", 0.0)])
    def test_scope_table_carries_the_share_tracing_the_step_set(
            self, tmp_path, conv_backend, share):
        """ISSUE 37: ``fused/stem_handed_share`` is set where the fused
        step is traced — T of an update's T+1 slots where the agent's
        acting steps hand their stem activation over, 0 where the agent
        declares nothing — and the scope table's notes hold the same
        number."""
        import json

        import numpy as np

        from scalable_agent_tpu.envs.device import make_device_env
        from scalable_agent_tpu.models import ImpalaAgent
        from scalable_agent_tpu.obs import get_registry
        from scalable_agent_tpu.parallel import MeshSpec, make_mesh
        from scalable_agent_tpu.runtime import Learner, LearnerHyperparams
        from scalable_agent_tpu.runtime.ingraph import InGraphTrainer

        env = make_device_env("fake_benchmark", height=16, width=24)
        agent = ImpalaAgent(num_actions=env.num_actions,
                            conv_backend=conv_backend)
        learner = Learner(
            agent, LearnerHyperparams(), make_mesh(
                MeshSpec(data=1, model=1), devices=jax.devices()[:1]),
            frames_per_update=5 * 4)
        trainer = InGraphTrainer(agent, learner, env, 5, 4)
        gauge = get_registry().gauge("fused/stem_handed_share")
        gauge.set(-1.0)
        state, carry = jax.eval_shape(trainer.init, jax.random.key(0))
        trainer.train_step.lower(state, carry, np.int32(0))
        assert gauge.value == share
        path = kernels_lib.write_op_scopes(
            str(tmp_path / "trace.p0.7.json"), _FRAMES_CHANGE)
        assert json.load(open(path))["notes"]["stem_handed_share"] == share


class TestTraceJoin:
    def test_harvest_roundtrip(self, tmp_path, monkeypatch):
        """Profile a compiled program, harvest, and verify the
        acceptance identity: per-kernel FLOPs sum to the MFU numerator
        handed in (the XLA cost-analysis total)."""
        compiled, args = _compiled_conv_dot()
        compiled(*args)  # warm
        profile_dir = str(tmp_path / "prof")
        executions = 4
        with jax.profiler.trace(profile_dir):
            for _ in range(executions):
                out = compiled(*args)
            jax.block_until_ready(out)

        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        flops_total = float(cost["flops"])
        from scalable_agent_tpu.obs import MetricsRegistry

        registry = MetricsRegistry()
        table = kernels_lib.harvest(
            profile_dir, compiled.as_text(), flops_total,
            peak_flops=1e12, logdir=str(tmp_path / "run"),
            registry=registry, executions=executions)
        assert table is not None and table["kernels"], table

        # THE identity: per-kernel FLOPs sum to the ledger-MFU
        # numerator (normalized attribution of XLA's own total).
        assert sum(row["flops"] for row in table["kernels"]) \
            == pytest.approx(flops_total, rel=1e-6)
        assert table["flops_total"] == flops_total

        # kernels.json persisted and re-readable.
        path = os.path.join(str(tmp_path / "run"), "kernels.json")
        assert os.path.exists(path)
        persisted = json.load(open(path))
        assert persisted["dominant_kernel"] == table["dominant_kernel"]

        # Roofline MFU is populated against the synthetic peak and the
        # rows aggregate real calls from the window.
        dominant = table["kernels"][0]
        assert dominant["calls"] >= executions
        assert 0 < dominant["mfu"] <= 1.0 or dominant["mfu"] >= 0

        # Registry gauges for the verdict + the stall hand-off.
        snap = registry.snapshot()
        assert "kernel/matched_time_frac" in snap
        assert kernels_lib.last_dominant(registry)[0] \
            == table["dominant_kernel"]
        assert kernels_lib.last_dominant(MetricsRegistry()) is None

    def test_harvest_without_traces_returns_none(self, tmp_path):
        assert kernels_lib.harvest(
            str(tmp_path / "nothing"), "", 0.0, None, None) is None

    def test_trace_events_filter_by_hlo_module(self, tmp_path):
        """XLA instruction names are unique only per module: an event
        annotated with ANOTHER module's name (a concurrently-running
        actor_step, say) must not be joined to the update's same-named
        instruction; unannotated events pass through."""
        path = str(tmp_path / "x.trace.json")
        events = [
            {"ph": "X", "name": "fusion.1", "dur": 10.0,
             "args": {"hlo_module": "jit_update"}},
            {"ph": "X", "name": "fusion.1", "dur": 999.0,
             "args": {"hlo_module": "jit_actor_step"}},
            {"ph": "X", "name": "fusion.2", "dur": 5.0},  # unannotated
        ]
        with open(path, "w") as f:
            json.dump({"traceEvents": events}, f)
        out = kernels_lib.load_trace_kernel_events(
            path, module="jit_update")
        assert out["fusion.1"] == {"time_us": 10.0, "calls": 1.0}
        assert out["fusion.2"] == {"time_us": 5.0, "calls": 1.0}
        # No filter: everything aggregates by name (legacy behavior).
        both = kernels_lib.load_trace_kernel_events(path)
        assert both["fusion.1"]["time_us"] == pytest.approx(1009.0)
        # The module name harvest() derives comes off the HLO header.
        assert kernels_lib.hlo_module_name(
            "HloModule jit_update, is_scheduled=true\n") == "jit_update"


class TestReportKernels:
    def _write_minimal_prom(self, logdir):
        os.makedirs(logdir, exist_ok=True)
        with open(os.path.join(logdir, "metrics.prom"), "w") as f:
            f.write("# TYPE impala_ledger_mfu gauge\n"
                    "impala_ledger_mfu 0.1\n")

    def test_report_json_names_dominant_kernel(self, tmp_path, capsys):
        from scalable_agent_tpu.obs import report

        logdir = str(tmp_path / "run")
        self._write_minimal_prom(logdir)
        kernels_lib.write_kernels_json(logdir, {
            "schema_version": 1,
            "flops_total": 1e9,
            "matched_time_frac": 0.9,
            "kernels": [
                {"name": "loss_grad_fusion", "time_us": 900.0,
                 "time_share": 0.9, "calls": 5, "flops": 9e8,
                 "intensity": 12.0, "mfu": 0.11},
                {"name": "optimizer_fusion", "time_us": 100.0,
                 "time_share": 0.1, "calls": 5, "flops": 1e8,
                 "intensity": 3.0, "mfu": 0.55},
            ],
            "worst_kernel": "loss_grad_fusion",
            "worst_kernel_mfu": 0.11,
            "dominant_kernel": "loss_grad_fusion",
            "dominant_time_share": 0.9,
        })
        assert report.main(["--json", logdir]) == 0
        payload = json.loads(capsys.readouterr().out)
        assert payload["kernels"]["dominant"] == "loss_grad_fusion"
        assert payload["kernels"]["worst"] == "loss_grad_fusion"
        assert payload["kernels"]["rows"][0]["mfu"] == 0.11

        # The text rendering carries the same verdict.
        assert report.main([logdir]) == 0
        out = capsys.readouterr().out
        assert "worst kernels (this run's profile window)" in out
        assert "loss_grad_fusion" in out
        assert "worst kernel: loss_grad_fusion" in out

    def test_report_names_conv0_gradw_from_bench_artifact(
            self, tmp_path, capsys, bench_history):
        """The history's newest artifact (r05, truncated) carries the
        hand-measured kernel rooflines; the report must surface them
        automatically and name conv0_gradw (0.107 MFU) as the worst
        kernel."""
        from scalable_agent_tpu.obs import report

        logdir = str(tmp_path / "run")
        self._write_minimal_prom(logdir)
        payload = report.build_report(logdir, bench_dir=bench_history)
        bench_kernels = payload["bench_kernels"]
        assert bench_kernels is not None
        assert bench_kernels["worst"] == "conv0_gradw"
        assert bench_kernels["worst_mfu"] == pytest.approx(0.107)
        names = {row["name"] for row in bench_kernels["rows"]}
        assert "conv0_gradw" in names

        assert report.main([logdir, "--bench_dir", bench_history]) == 0
        out = capsys.readouterr().out
        assert "worst kernels (newest bench artifact)" in out
        assert "worst kernel: conv0_gradw" in out

        assert report.main(["--json", logdir,
                            "--bench_dir", bench_history]) == 0
        machine = json.loads(capsys.readouterr().out)
        assert machine["bench_kernels"]["worst"] == "conv0_gradw"

    def test_bench_kernels_absent_outside_a_checkout(self, tmp_path):
        from scalable_agent_tpu.obs import report

        logdir = str(tmp_path / "run")
        self._write_minimal_prom(logdir)
        payload = report.build_report(
            logdir, bench_dir=str(tmp_path / "empty"))
        assert payload["bench_kernels"] is None


def test_traced_driver_run_writes_kernel_ledger(tmp_path, monkeypatch,
                                                capsys):
    """Tier-1 acceptance: a --profile_dir driver run on the CPU rig
    writes kernels.json, publishes kernel/* gauges into the prom
    snapshot, and the report CLI names the dominant kernel from it."""
    from scalable_agent_tpu.config import Config
    from scalable_agent_tpu.driver import train as run_train
    from scalable_agent_tpu.obs import report

    monkeypatch.setenv("SCALABLE_AGENT_LEDGER_MFU_PEAK", "1e12")
    config = Config(
        mode="train",
        logdir=str(tmp_path / "run"),
        level_name="fake_small",
        num_actors=4,
        batch_size=2,
        unroll_length=4,
        num_action_repeats=1,
        total_environment_frames=24,  # 3 updates of 8 frames
        height=16,
        width=16,
        num_env_workers_per_group=2,
        compute_dtype="float32",
        checkpoint_interval_s=1e9,
        log_interval_s=0.0,
        profile_dir=str(tmp_path / "profile"),
        profile_start_update=1,
        profile_num_updates=1,
        seed=5,
    )
    metrics = run_train(config)
    assert metrics["env_frames"] == 24

    # The profile window left a device trace and the harvest joined it.
    kernels_path = os.path.join(config.logdir, "kernels.json")
    assert os.path.exists(kernels_path), glob.glob(
        os.path.join(config.logdir, "*"))
    table = json.load(open(kernels_path))
    assert table["kernels"], table
    assert table["dominant_kernel"]
    assert table["flops_total"] > 0
    assert sum(row["flops"] for row in table["kernels"]) \
        == pytest.approx(table["flops_total"], rel=1e-6)

    # kernel/* gauges rode the prom snapshot.
    prom = open(os.path.join(config.logdir, "metrics.prom")).read()
    assert "impala_kernel_matched_time_frac" in prom
    assert "impala_kernel_dominant_time_share" in prom

    # The report names the dominant kernel, machine-readably.
    assert report.main(["--json", config.logdir]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert payload["kernels"]["dominant"] == table["dominant_kernel"]
