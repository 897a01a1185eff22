"""Sharding rules, ZeRO-1 specs, and the HLO cost parser."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as shlib
from repro.launch import hlocost
from repro.models.common import PSpec


def test_logical_to_pspec():
    rules = {"vocab": "model", "embed": None, "batch": ("pod", "data")}
    ps = shlib.logical_to_pspec(("vocab", "embed"), rules)
    assert ps == P("model")
    ps = shlib.logical_to_pspec(("batch", None, "vocab"), rules)
    assert ps == P(("pod", "data"), None, "model")


def _make_mesh():
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def test_evenly_shardable_drops_indivisible():
    mesh = _make_mesh()
    # 1-device mesh: everything trivially divisible
    ps = shlib._evenly_shardable(P("model"), (10,), mesh)
    assert ps == P("model")


def test_zero1_shards_largest_free_dim():
    mesh = _make_mesh()
    ps = shlib.zero1_spec(P(None, "model"), (8, 16), mesh, axis="data")
    assert ps == P("data", "model")


SYNTH_HLO = """\
HloModule test

%body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,8] get-tuple-element(%p), index=1
  %d = f32[8,8]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,8]{1,0} all-reduce(%d), replica_groups={{0,1,2,3}}, to_apply=%add
  %one = s32[] constant(1)
  %ni = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[8,8]) tuple(%ni, %ar)
}

%cond (p: (s32[], f32[8,8])) -> pred[] {
  %p = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(5)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (a: f32[8,8]) -> (s32[], f32[8,8]) {
  %a = f32[8,8] parameter(0)
  %z = s32[] constant(0)
  %t0 = (s32[], f32[8,8]) tuple(%z, %a)
  ROOT %w = (s32[], f32[8,8]) while(%t0), condition=%cond, body=%body
}
"""


def test_hlocost_trip_count_multiplies():
    s = hlocost.analyze(SYNTH_HLO)
    # dot: 2*8*8*8 = 1024 flops, x5 trips
    assert s.flops == pytest.approx(1024 * 5, rel=0.01)
    # all-reduce wire at TPU-native width (f32 charged 2B):
    # 2*(g-1)/g * 128B = 192B, x5 trips
    assert s.wire_bytes == pytest.approx(192 * 5)
    assert 5 in s.trip_counts.values()


def test_hlocost_backend_config_trip():
    hlo = SYNTH_HLO.replace(
        "condition=%cond, body=%body",
        'condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"7"}}')
    s = hlocost.analyze(hlo)
    assert s.flops == pytest.approx(1024 * 7, rel=0.01)


def test_batch_shardings_replicate_small_batch():
    mesh = _make_mesh()
    tree = {"tokens": jax.ShapeDtypeStruct((1, 8), jnp.int32)}
    sh = shlib.batch_shardings(tree, mesh)
    # batch=1 on size-1 axes: sharded-over-1 == replicated, both legal
    assert sh["tokens"].spec in (P(), P("data"), P(("data",)))


@pytest.mark.parametrize("n_e,n_f", [(4, 1), (1, 2), (2, 2)],
                         ids=["experts", "hidden", "both"])
def test_routed_experts_add_up_over_shards(n_e, n_f):
    """What ``MeshSharding.expert_map`` has each shard compute: the
    routed sum over the experts e0 .. e0 + E/n_e and a 1/n_f slice of
    each expert's hidden width.  The shards' partial sums add up to the
    whole layer's, and masked rows get nothing."""
    import numpy as np
    from repro.models import moe

    t, k, e, d, f, lm = 24, 3, 8, 16, 12, 2
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    xt = jax.random.normal(ks[0], (t, d), jnp.float32)
    top_i = jax.random.randint(ks[1], (t, k), 0, e)
    top_p = jax.random.uniform(ks[2], (t, k))
    valid = jnp.arange(t) < t - 5
    wg, wu = (jax.random.normal(kk, (lm, e, d, f)) for kk in
              jax.random.split(ks[3]))
    wd = jax.random.normal(ks[4], (lm, e, f, d))
    layer = jnp.int32(1)
    whole = moe.routed_sum(xt, top_i, top_p, valid, wg, wu, wd, layer,
                           backend="xla")
    es, fs = e // n_e, f // n_f
    parts = sum(
        moe.routed_sum(xt, top_i, top_p, valid,
                       wg[:, a * es:(a + 1) * es, :, b * fs:(b + 1) * fs],
                       wu[:, a * es:(a + 1) * es, :, b * fs:(b + 1) * fs],
                       wd[:, a * es:(a + 1) * es, b * fs:(b + 1) * fs],
                       layer, a * es, backend="xla")
        for a in range(n_e) for b in range(n_f))
    # float32 sums taken in another order
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               rtol=1e-4, atol=1e-4)
    assert not np.asarray(whole)[t - 5:].any()
