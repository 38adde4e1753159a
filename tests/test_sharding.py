"""dist.sharding utilities + the HLO analyzer on a synthetic module."""

import numpy as np
import jax
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import add_data_axis, prune_spec
from repro.launch.hlo_analysis import analyze, _parse_computations


class FakeMesh:
    axis_names = ("data", "model")
    axis_sizes = (4, 2)


def test_prune_spec_drops_nondivisible():
    spec = prune_spec(P("data", "model"), (1, 64), FakeMesh())
    assert tuple(spec) == (None, "model")


def test_prune_spec_keeps_divisible():
    spec = prune_spec(P("data", "model"), (8, 64), FakeMesh())
    assert tuple(spec) == ("data", "model")


def test_prune_tuple_axes():
    spec = prune_spec(P(("data", "model"), None), (8, 3), FakeMesh())
    assert tuple(spec) == (("data", "model"), None)
    spec = prune_spec(P(("data", "model"), None), (4, 3), FakeMesh())
    assert tuple(spec) == (None, None)


def test_add_data_axis_first_free_dim():
    out = add_data_axis(P(None, "model", None), (64, 32, 48), dp_size=16)
    assert tuple(out) == ("data", "model", None)


def test_add_data_axis_skip_dims():
    out = add_data_axis(P(None, "model", None), (64, 32, 48), dp_size=16,
                        skip_dims=(0,))
    assert tuple(out) == (None, "model", "data")


def test_add_data_axis_never_double_shards():
    out = add_data_axis(P("data", None), (64, 32), dp_size=16)
    assert tuple(out) == ("data", None)


SYNTH_HLO = """
HloModule synth, entry_computation_layout={()->f32[8,8]{1,0}}

%body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,8]{1,0} get-tuple-element(%p), index=1
  %d = f32[8,8]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,8]{1,0} all-reduce(%d), replica_groups={}, to_apply=%add
  ROOT %t = (s32[], f32[8,8]{1,0}) tuple(%i, %ar)
}

%cond (p: (s32[], f32[8,8])) -> pred[] {
  %p = (s32[], f32[8,8]{1,0}) parameter(0)
  ROOT %c = pred[] constant(false)
}

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

ENTRY %main () -> f32[8,8] {
  %init = (s32[], f32[8,8]{1,0}) tuple()
  %w = (s32[], f32[8,8]{1,0}) while(%init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"5"}}
  ROOT %out = f32[8,8]{1,0} get-tuple-element(%w), index=1
}
"""


def test_hlo_analyzer_trip_counts():
    m = analyze(SYNTH_HLO)
    # dot: 2*8*8*8 = 1024 flops, x5 trips
    assert m.flops == 1024 * 5
    # all-reduce: 8*8*4 bytes x5
    assert m.collective_bytes["all-reduce"] == 256 * 5
    assert m.collective_counts["all-reduce"] == 5


def test_hlo_parser_counts_computations():
    comps, entry = _parse_computations(SYNTH_HLO)
    assert entry == "main"
    assert set(comps) == {"body", "cond", "add", "main"}


def test_shard_hint_constrains_under_use_mesh():
    # the mesh jax.set_mesh installs is the one shard_hint resolves against:
    # a constraint inside the context, the identity outside it
    from repro.dist.sharding import shard_hint
    from repro.launch.mesh import use_mesh
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)

    def hinted(x):
        return shard_hint(x, P("data", "model")) * 2

    x = np.ones((4, 8), np.float32)
    with use_mesh(mesh):
        inside = str(jax.make_jaxpr(hinted)(x))
        out = jax.jit(hinted)(x)
    assert "sharding_constraint" in inside
    assert "sharding_constraint" not in str(jax.make_jaxpr(hinted)(x))
    np.testing.assert_array_equal(np.asarray(out), 2 * x)
