"""Sharding rules: divisibility-aware resolution, layouts, cache rules."""

import jax
import numpy as np
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_config
from repro.distributed.sharding import (
    Rules,
    activation_rules,
    cache_rules,
    cache_rules_dp,
    param_rules,
    tree_specs,
)
from repro.models.common import ParamSpec
from repro.models.lm import model_schema

AXES = {"pod": 2, "data": 16, "model": 16}


def test_spec_divisibility_drops_nondividing_axes():
    r = param_rules(zero=3)
    # kv_heads 4 can't take a 16-way axis → dropped
    spec = r.spec_for(("kv_heads", "embed"), AXES, (4, 512))
    assert spec == P(None, "data")
    # heads 128 can
    spec2 = r.spec_for(("heads", "embed"), AXES, (128, 512))
    assert spec2 == P("model", "data")


def test_spec_axis_used_once_per_leaf():
    r = param_rules(zero=3)
    # experts grabs "model"; ff must not reuse it
    spec = r.spec_for(("experts", "embed", "ff"), AXES, (256, 7168, 2048))
    assert spec == P("model", "data", None)


def test_dp_layout_spreads_over_both_axes():
    r = param_rules(layout="dp")
    spec = r.spec_for(("vocab", "embed"), AXES, (49152, 576))
    assert spec[0] == ("data", "model")


def test_activation_rules_batch_fitting():
    mesh = AbstractMesh((4, 2), ("data", "model"))
    r = activation_rules(8, mesh)
    assert r.table["batch"] == ("data",)
    r2 = activation_rules(3, mesh)  # indivisible → unsharded
    assert r2.table["batch"] is None
    r3 = activation_rules(8, mesh, layout="dp")
    assert r3.table["batch"] == ("data", "model")


def test_cache_rules_seq_takes_leftover_axes():
    mesh = AbstractMesh((4, 2), ("data", "model"))
    r = cache_rules(1, mesh)  # batch=1: nothing fits
    assert r.table["batch"] is None
    assert "model" in r.table["seq"] and "data" in r.table["seq"]
    rdp = cache_rules_dp(4, mesh)
    assert rdp.table["batch"] == ("data",)


@pytest.mark.parametrize("arch", ["deepseek-v3-671b", "smollm-135m", "jamba-1.5-large-398b"])
def test_param_specs_resolve_for_real_schemas(arch):
    mesh = AbstractMesh((2, 2), ("data", "model"))
    schema = model_schema(get_config(arch).reduced())
    specs = tree_specs(schema, param_rules(zero=3), mesh)
    # every leaf got a PartitionSpec and no axis repeats within a leaf
    leaves = jax.tree_util.tree_leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert leaves
    for sp in leaves:
        used = [a for dim in sp for a in ((dim,) if isinstance(dim, str) else (dim or ()))]
        assert len(used) == len(set(used)), sp
