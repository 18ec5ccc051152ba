"""Logical-axis sharding resolution + HLO roofline analyzer."""
import jax
import numpy as np

import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import (
    axis_rules,
    default_rules,
    shardings_like,
    spec_for,
)
from repro.launch.mesh import make_mesh
from repro.launch.roofline import analyze_hlo

SCAN_HLO = """\
HloModule jit_h, is_scheduled=true

%fused_computation (param_0.1: f32[1,256,256]) -> f32[256,256] {
  %param_0.1 = f32[1,256,256]{2,0,1} parameter(0)
  ROOT %bitcast.1 = f32[256,256]{1,0} bitcast(%param_0.1)
}

%region_0.1_spmd (param: (s32[], f32[64,256], f32[10,64,256])) -> (s32[], f32[64,256], f32[10,64,256]) {
  %param = (s32[], f32[64,256]{1,0}, f32[10,64,256]{2,0,1}) parameter(0)
  %get-tuple-element.25 = f32[64,256]{1,0} get-tuple-element(%param), index=1
  %get-tuple-element.26 = f32[10,64,256]{2,0,1} get-tuple-element(%param), index=2
  %wrapped_dynamic-slice = f32[1,64,256]{2,0,1} dynamic-slice(%get-tuple-element.26), dynamic_slice_sizes={1,64,256}
  %all-gather = f32[1,256,256]{2,0,1} all-gather(%wrapped_dynamic-slice), channel_id=1, replica_groups=[1,4]<=[4], dimensions={1}
  %copy_bitcast_fusion = f32[256,256]{1,0} fusion(%all-gather), kind=kLoop, calls=%fused_computation
  %dot = f32[64,256]{1,0} dot(%get-tuple-element.25, %copy_bitcast_fusion), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %tuple = (s32[], f32[64,256]{1,0}, f32[10,64,256]{2,0,1}) tuple(%get-tuple-element.25, %dot, %get-tuple-element.26)
}

ENTRY %main.3_spmd (param.2: f32[64,256], param.3: f32[10,64,256]) -> f32[64,256] {
  %param.2 = f32[64,256]{1,0} parameter(0)
  %param.3 = f32[10,64,256]{2,0,1} parameter(1)
  %tuple.6 = (s32[], f32[64,256]{1,0}, f32[10,64,256]{2,0,1}) tuple(%param.2, %param.2, %param.3)
  %while.8 = (s32[], f32[64,256]{1,0}, f32[10,64,256]{2,0,1}) while(%tuple.6), condition=%region_1.2_spmd, body=%region_0.1_spmd, backend_config={"known_trip_count":{"n":"10"}}
  ROOT %get-tuple-element.30 = f32[64,256]{1,0} get-tuple-element(%while.8), index=1
}
"""


def test_spec_resolution_and_taken_axes():
    mesh = make_mesh((1, 1), ("data", "model"))
    rules = default_rules(multi_pod=False)
    # heads -> model; second use of model in the same spec is dropped
    s = spec_for(("embed", "heads"), rules, mesh)
    assert s == P("data", "model")
    s2 = spec_for(("heads", "mlp"), rules, mesh)
    assert s2 == P("model", None)  # mlp loses: model already taken
    # pod axis silently dropped on a single-pod mesh
    rules_mp = default_rules(multi_pod=True)
    s3 = spec_for(("batch",), rules_mp, mesh)
    assert s3 == P("data")


def test_logical_constraint_noop_without_rules():
    import jax.numpy as jnp
    from repro.distributed.sharding import logical_constraint
    x = jnp.ones((4, 4))
    y = logical_constraint(x, "batch", None)
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_shardings_like_tuple_leaves():
    mesh = make_mesh((1, 1), ("data", "model"))
    rules = default_rules()
    template = {"w": jax.ShapeDtypeStruct((8, 8), np.float32),
                "inner": {"b": jax.ShapeDtypeStruct((8,), np.float32)}}
    specs = {"w": ("embed", "mlp"), "inner": {"b": (None,)}}
    sh = shardings_like(template, specs, rules, mesh)
    assert sh["w"].spec == P("data", "model")
    assert sh["inner"]["b"].spec == P(None)


def test_analyzer_trip_scaling_and_collectives():
    a = analyze_hlo(SCAN_HLO)
    assert a.flops == 10 * 2 * 64 * 256 * 256          # dot x10 trips
    assert a.bytes_collective == 10 * 1 * 64 * 256 * 4  # all-gather operand
    assert a.coll_breakdown["all-gather"] == a.bytes_collective
    assert a.unresolved_dots == 0


def test_analyzer_skips_fusion_internals_for_bytes():
    a = analyze_hlo(SCAN_HLO)
    # bytes are counted at fusion boundaries only; the bitcast inside
    # %fused_computation must not be double counted. The fusion op itself
    # (result 256KB + operand 256KB) x 10 trips is included:
    assert a.bytes_hbm >= 10 * 2 * 256 * 256 * 4
    # and nothing from inside the fused computation:
    assert a.bytes_hbm < 60 * 1024 * 1024


@pytest.mark.parametrize("shape,expect", [
    ("f32[2,3]", 24), ("bf16[128]", 256), ("pred[8]", 8), ("s32[]", 4)])
def test_shape_bytes(shape, expect):
    from repro.launch.roofline import _shapes_in, _nbytes_many
    assert _nbytes_many(_shapes_in(shape)) == expect
