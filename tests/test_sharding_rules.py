"""Unit tests for the divisibility-aware sharding rules.

These run against a FAKE mesh description (no devices needed) by
exercising the rule functions with a real 1-device mesh where only the
axis-size arithmetic matters — so we monkey-create a Mesh-like object.
"""
import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.runtime import sharding as shr


class FakeMesh:
    """Duck-typed mesh: sharding rules only read .shape."""

    def __init__(self, shape: dict):
        self.shape = shape


MESH = FakeMesh({"data": 16, "model": 16})
MESH3 = FakeMesh({"pod": 2, "data": 16, "model": 16})


def _spec(name, shape, mesh=MESH):
    path = (jax.tree_util.DictKey(name),)
    return shr.param_spec(path, shape, mesh)


def test_column_parallel_prefers_last_dim():
    assert _spec("wq", (40, 6144, 6144)) == P(None, None, "model")
    assert _spec("w1", (4096, 11008)) == P(None, "model")


def test_row_parallel_prefers_second_to_last():
    assert _spec("wo", (40, 6144, 6144)) == P(None, "model", None)
    assert _spec("w2", (11008, 4096)) == P("model", None)


def test_vocab_parallel_with_fallback():
    # divisible vocab -> vocab dim
    assert _spec("embed", (49152, 6144)) == P("model", None)
    # odd vocab (seamless 256206) -> falls back to d_model
    assert _spec("embed", (256206, 1024)) == P(None, "model")
    assert _spec("lm_head", (1024, 256206)) == P("model", None)


def test_expert_sharding():
    assert _spec("we1", (61, 384, 7168, 2048)) == P(None, "model", None, None)


def test_norms_replicated():
    assert _spec("final_norm", (4096,)) == P()
    assert _spec("ln1", (40, 4096)) == P()


def test_packed_weight_codes_inherit_parent_rule():
    path = (jax.tree_util.DictKey("wq"), jax.tree_util.GetAttrKey("codes"))
    assert shr.param_spec(path, (36, 2048, 4096), MESH) == P(None, None, "model")
    path_sf = (jax.tree_util.DictKey("wq"), jax.tree_util.GetAttrKey("sf"))
    assert shr.param_spec(path_sf, (36, 1, 1), MESH) == P()


def _aux(weight, leaf, shape, mesh=MESH):
    path = (jax.tree_util.DictKey(weight), jax.tree_util.GetAttrKey(leaf))
    return shr.param_spec(path, shape, mesh)


def test_per_channel_scales_follow_sharded_out_dim():
    # column-parallel weight: codes shard N, per-channel sf shards the
    # SAME N — each shard dequantizes against its own scale columns
    assert _aux("wq", "sf", (36, 1, 4096)) == P(None, None, "model")
    assert _aux("w1", "sf", (1, 11008)) == P(None, "model")
    assert _aux("w1", "act_scale", (1, 11008)) == P(None, "model")
    # per-slice / per-tensor scales have no shardable extent
    assert _aux("w1", "sf", (36, 1, 1)) == P()
    # non-divisible out-dim: weight falls back, so do the scales
    assert _aux("wq", "sf", (1, 30)) == P()


def test_row_parallel_scales_replicate():
    # wo/w2 shard the contracting dim; every shard needs ALL out-channel
    # scales, so per-channel sf must NOT shard (the old rule's silent
    # replication was accidentally right here — now it is deliberate)
    assert _aux("wo", "sf", (1, 6144)) == P()
    assert _aux("w2", "sf", (36, 1, 4096)) == P()


def test_expert_scales_follow_expert_dim():
    # codes [L, E, K, N] shard E; per-slice sf [L, E, 1, 1] follows
    assert _aux("we1", "sf", (61, 384, 1, 1)) == P(None, "model", None, None)
    assert _aux("we2", "sf", (61, 384, 1, 1)) == P(None, "model", None, None)


def test_packed_tree_specs_align_codes_and_scales():
    """Spec-tree check on a real packed pytree: every PackedWeight's sf
    spec is consistent with its codes spec (no axis used by sf that the
    codes do not shard on the matching dim family)."""
    from repro.kernels.ops import PackedWeight, pack_weight
    from repro.core.elp_bsd import FORMAT_A

    def build():
        w_col = jax.random.normal(jax.random.PRNGKey(0), (4, 64, 128))
        w_row = jax.random.normal(jax.random.PRNGKey(1), (4, 128, 64))
        return {
            "blocks": {
                "wq": pack_weight(w_col, FORMAT_A, granularity="per_channel")[0],
                "w1": pack_weight(w_col, FORMAT_A, granularity="per_slice")[0],
                "wo": pack_weight(w_row, FORMAT_A, granularity="per_channel")[0],
            }
        }

    atree = jax.eval_shape(build)
    mesh = FakeMesh({"data": 2, "model": 4})
    specs = shr.param_specs(atree, mesh)
    b = specs["blocks"]
    assert b["wq"].codes == P(None, None, "model") and b["wq"].sf == P(None, None, "model")
    assert b["w1"].codes == P(None, None, "model") and b["w1"].sf == P()  # per-slice
    assert b["wo"].codes == P(None, "model", None) and b["wo"].sf == P()  # row-parallel
    assert isinstance(atree["blocks"]["wq"], PackedWeight)


def test_non_divisible_falls_back_to_replication():
    # 56-head q proj output 7168 divides; a deliberately odd dim doesn't
    assert _spec("wq", (10, 30, 30)) == P()


def test_input_spec_divisibility():
    assert shr.input_spec((256, 4096), MESH) == P(("data",), None)
    assert shr.input_spec((256, 4096), MESH3) == P(("pod", "data"), None)
    # long_500k batch=1: replicate
    assert shr.input_spec((1, 524288), MESH) == P(None, None)


def test_cache_spec_head_then_hd_then_seq():
    # kv=4 heads don't divide 16, hd=128 does
    s = shr.cache_spec((), (40, 128, 32768, 4, 128), MESH)
    assert tuple(s) == (None, "data", None, None, "model")
    # flash layout: seq takes the model axis
    s2 = shr.cache_spec((), (40, 128, 32768, 4, 128), MESH, prefer_seq=True)
    assert tuple(s2) == (None, "data", "model", None, None)


def test_cache_spec_kv_leaves_take_heads_first():
    """A dense cache's k/v put whole KV heads on each device when the
    heads divide the model axis (yi-34b: 8 over 4), so attention over
    the cache needs no collective; else head_dim, as before."""
    mesh = FakeMesh({"data": 1, "model": 4})
    for leaf in ("k", "v"):
        path = (jax.tree_util.DictKey(leaf),)
        s = shr.cache_spec(path, (60, 32, 1280, 8, 128), mesh)
        assert tuple(s) == (None, "data", None, "model", None)
        s = shr.cache_spec(path, (2, 4, 48, 2, 16), mesh)
        assert tuple(s) == (None, "data", None, None, "model")


def test_mark_splits_records_each_packed_weights_split():
    from repro.core.elp_bsd import FORMAT_A
    from repro.kernels.ops import pack_weight

    def build():
        w_col = jax.random.normal(jax.random.PRNGKey(0), (3, 64, 128))
        w_row = jax.random.normal(jax.random.PRNGKey(1), (3, 128, 64))
        experts = jax.random.normal(jax.random.PRNGKey(2), (3, 4, 64, 32))
        return {
            "wq": pack_weight(w_col, FORMAT_A, granularity="per_slice")[0],
            "wo": pack_weight(w_row, FORMAT_A, granularity="per_slice")[0],
            "we1": pack_weight(experts, FORMAT_A, granularity="per_slice")[0],
            "ln1": jax.numpy.zeros((3, 64)),
        }

    atree = jax.eval_shape(build)
    mesh = FakeMesh({"data": 2, "model": 4})
    marked = shr.mark_splits(atree, shr.param_specs(atree, mesh), mesh)
    assert marked["wq"].split == (None, "model") and marked["wq"].mesh is mesh
    assert marked["wo"].split == ("model", None)
    assert marked["we1"].split == (None, None)  # the expert dim is split, not K or N
    assert marked["ln1"] is atree["ln1"] and atree["wq"].split is None


def test_zero1_extends_over_data():
    base = P(None, None, "model")
    z = shr.zero1_spec(base, (40, 4096, 11008), MESH)
    assert tuple(z) == (None, "data", "model")
