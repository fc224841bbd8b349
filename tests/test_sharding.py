"""Sharding rules + launch machinery (runs on the single real CPU device by
using trivial 1x1 meshes, plus pure-logic tests for the rules table)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.launch.analysis import (_shape_bytes, _split_computations,
                                   collective_bytes)
from repro.launch.cost_model import cell_cost
from repro.launch.sharding_rules import (LONG_CTX_OVERRIDES, TRAIN_RULES,
                                         check_divisibility, partition_spec,
                                         resolve_rules)
from repro.configs import ARCH_NAMES, get
from repro.launch.mesh import make_local_mesh
from repro.models.config import SHAPES
from repro.models.params import param_count
from repro.models.transformer import model_specs


class FakeMesh:
    def __init__(self, names, shape):
        self.axis_names = names
        self.shape = dict(zip(names, shape))


MESH2 = FakeMesh(("data", "model"), (16, 16))
MESH3 = FakeMesh(("pod", "data", "model"), (2, 16, 16))


def test_partition_spec_basic():
    rules = resolve_rules()
    assert partition_spec(("batch", None), rules, MESH3) == P(("pod", "data"), None)
    assert partition_spec(("batch", None), rules, MESH2) == P("data", None)
    assert partition_spec(("embed", "ff"), rules, MESH3) == P(("pod", "data"), "model")
    assert partition_spec(("vocab", "embed"), rules, MESH2) == P("model", "data")


def test_partition_spec_no_axis_reuse():
    rules = resolve_rules()
    # two dims both wanting "model": second gets None
    spec = partition_spec(("heads", "ff"), rules, MESH2)
    assert spec == P("model", None)


def test_long_ctx_overrides():
    rules = resolve_rules(TRAIN_RULES, LONG_CTX_OVERRIDES)
    spec = partition_spec(("layers", "batch", "kv_heads", "cache_seq", "head_dim"),
                          rules, MESH3)
    assert spec == P(None, None, None, ("data", "model"), None)


def test_divisibility_check():
    assert check_divisibility((32, 64), P("data", "model"), MESH2)
    assert not check_divisibility((31, 64), P("data", None), MESH2)


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_all_params_divisible_on_production_mesh(arch):
    """Every param of every arch shards evenly on both production meshes —
    the static guarantee behind the dry-run."""
    cfg = get(arch)
    specs = model_specs(cfg)
    rules = resolve_rules()
    leaves = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: hasattr(x, "logical"))
    for mesh in (MESH2, MESH3):
        for spec in leaves:
            ps = partition_spec(spec.logical, rules, mesh)
            assert check_divisibility(spec.shape, ps, mesh), \
                (arch, spec.shape, spec.logical, ps)


def test_hlo_shape_bytes():
    assert _shape_bytes("f32[8,4]") == 128
    assert _shape_bytes("(bf16[2,2], s32[3])") == 8 + 12
    assert _shape_bytes("pred[16]") == 16


def test_collective_parser_trip_counts():
    hlo = """
%body (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %ag = f32[8] all-gather(%x), replica_groups={{0,1}}, dimensions={0}
  ROOT %t = tuple(...)
}

%cond (p: (s32[], f32[4])) -> pred[] {
  %c = s32[] constant(5)
  ROOT %cmp = pred[] compare(%i, %c), direction=LT
}

ENTRY %main (a: f32[4]) -> f32[4] {
  %ar = f32[16] all-reduce(%a), replica_groups={{0,1,2,3}}, to_apply=%add
  %w = (s32[], f32[4]) while(%init), condition=%cond, body=%body
  ROOT %r = f32[4] get-tuple-element(%w), index=1
}
"""
    out = collective_bytes(hlo)
    # all-gather: 8*4 bytes * (n-1)/n=0.5 * 5 trips = 80
    assert out["all-gather"] == pytest.approx(80.0)
    # all-reduce: 16*4 * 2 * 0.75 = 96
    assert out["all-reduce"] == pytest.approx(96.0)
    assert out["n_all-gather"] == 5


@pytest.mark.parametrize("arch", ["llama3.2-1b", "kimi-k2-1t-a32b", "rwkv6-3b"])
def test_cost_model_sane(arch):
    """Analytic FLOPs within sane factors of 6·N·D for train cells."""
    cfg = get(arch)
    cell = SHAPES[0]  # train_4k
    c = cell_cost(cfg, cell)
    assert c.flops > 0 and c.hbm_bytes > 0
    ratio = c.model_flops / c.flops
    assert 0.3 < ratio <= 1.1, (arch, ratio)  # attention/router overhead only


def test_param_counts_match_public_numbers():
    """Sanity anchors against published sizes (loose tolerances — our configs
    are per the assignment table, not the exact HF checkpoints)."""
    expect = {
        "llama3.2-1b": (1.0e9, 1.7e9),
        "phi3-mini-3.8b": (3.0e9, 4.5e9),
        "kimi-k2-1t-a32b": (0.8e12, 1.4e12),
        "qwen2-vl-72b": (6.0e10, 9.0e10),
        "olmoe-1b-7b": (6.0e9, 8.0e9),
    }
    for arch, (lo, hi) in expect.items():
        n = param_count(model_specs(get(arch)))
        assert lo < n < hi, (arch, n)


def test_rules_regimes_per_cell_kind():
    """§Perf regimes: weight-gathered for train/prefill, TP for decode,
    FSDP kept at decode only for archs that don't fit model-axis-only."""
    from repro.launch.input_specs import rules_for_cell
    from repro.models.config import SHAPES

    train, prefill, decode, long = SHAPES
    assert rules_for_cell(train, get("llama3.2-1b")).get("__gather_weights__")
    assert rules_for_cell(prefill, get("llama3.2-1b")).get("__gather_weights__")
    assert not rules_for_cell(decode, get("llama3.2-1b")).get("__gather_weights__")
    # gemma3-12b fits model-only at decode → weights replicated over DP
    assert rules_for_cell(decode, get("gemma3-12b"))["embed"] == ()
    # kimi-k2 (1T) does not → keeps FSDP sharding at decode
    assert rules_for_cell(decode, get("kimi-k2-1t-a32b"))["embed"] == ("pod", "data")


def test_constrain_noop_without_ctx():
    from repro.models.sharding_ctx import constrain
    x = jnp.ones((4, 4))
    assert constrain(x, ("batch", None)) is x


def test_constrain_divisibility_fallback():
    """24 heads on model=16 must fall back to unsharded, not crash."""
    import os
    if jax.device_count() < 2:
        mesh = make_local_mesh(1, 1)
    else:
        mesh = make_local_mesh(1, jax.device_count())
    from repro.models.sharding_ctx import activation_sharding, constrain
    from repro.launch.sharding_rules import resolve_rules
    with activation_sharding(mesh, resolve_rules()):
        x = jnp.ones((2, 24, 8))

        def f(x):
            return constrain(x, (None, "heads", None)) * 2

        out = jax.jit(f)(x)  # lowering must succeed regardless of mesh size
        assert out.shape == (2, 24, 8)


def test_sparse_weight_shardings():
    """train.sparse_weight_shardings: v_* BalancedCOO value streams shard
    tiles over the DP axis; dense leaves map to None; non-dividing tile
    counts fall back to replicated."""
    from repro.launch.mesh import make_local_mesh
    from repro.train import sparse_weight_shardings

    n = jax.device_count()
    mesh = make_local_mesh(n, 1)
    params = {"blocks": {"v_gate": jnp.ones((4, n * 2, 16)),
                         "v_up": jnp.ones((n * 2, 16)),
                         "v_odd": jnp.ones((max(n + 1, 3), 16)) if n > 1
                         else jnp.ones((3, 16)),
                         "w_up": jnp.ones((8, 8))}}
    sh = sparse_weight_shardings(params, mesh)
    assert sh["blocks"]["w_up"] is None
    assert sh["blocks"]["v_gate"].spec == P(None, "data", None)
    assert sh["blocks"]["v_up"].spec == P("data", None)
    if n > 1:  # n+1 tiles don't divide n → replicated fallback
        assert sh["blocks"]["v_odd"].spec == P()
    # the shardings place: device_put of the sparse leaves succeeds
    leaf = jax.device_put(params["blocks"]["v_gate"], sh["blocks"]["v_gate"])
    assert leaf.sharding == sh["blocks"]["v_gate"]


def test_sparse_weight_rules_marker():
    from repro.launch.sharding_rules import SPARSE_WEIGHT_RULES
    assert SPARSE_WEIGHT_RULES["tiles"] == ("pod", "data")
    assert SPARSE_WEIGHT_RULES["__sparse_shard_axis__"] == "data"


def test_topk_rows_matches_lax():
    from repro.models.moe import _topk_rows
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((32, 16)).astype(np.float32))
    v1, i1 = _topk_rows(x, 4)
    v2, i2 = jax.lax.top_k(x, 4)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
