"""Sharding-rule resolution unit tests.

These run on the single real CPU device: they construct a Mesh over one
device but exercise the pure resolution logic with synthetic axis sizes via
a fake mesh shim where needed.
"""
import types

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec

from repro import sharding as sh


class FakeMesh:
    """Duck-typed mesh: only .shape and .axis_names are consulted."""

    def __init__(self, shape_map):
        self.shape = shape_map
        self.axis_names = tuple(shape_map)


MESH = FakeMesh({"data": 16, "model": 16})
MESH3 = FakeMesh({"pod": 2, "data": 16, "model": 16})


def test_spec_for_basic_tp():
    rules = sh.server_rules(MESH)
    spec = sh.spec_for((2048, 8192), ("embed", "ff"), rules, MESH)
    assert spec == PartitionSpec("data", "model")


def test_spec_for_divisibility_fallback():
    rules = sh.server_rules(MESH)
    rep = sh.ShardingReport()
    spec = sh.spec_for((49155,), ("vocab",), rules, MESH, rep)
    assert spec == PartitionSpec(None)
    assert any("49155" in f for f in rep.fallbacks)


def test_spec_for_partial_prefix():
    """A dim divisible by a prefix of the assigned axes shards partially."""
    rules = {"batch": ("data", "model")}
    rep = sh.ShardingReport()
    spec = sh.spec_for((128,), ("batch",), rules, MESH, rep)
    # 128 % 256 != 0 but 128 % 16 == 0 -> partial shard over data
    assert spec == PartitionSpec("data")
    assert any("partial" in f for f in rep.fallbacks)


def test_spec_axes_not_reused_across_dims():
    rules = {"a": ("model",), "b": ("model",)}
    spec = sh.spec_for((64, 64), ("a", "b"), rules, MESH)
    assert spec == PartitionSpec("model", None)   # second dim can't reuse


def test_client_rules_replicate_embed():
    r = sh.client_rules(MESH)
    assert r["embed"] == ()
    assert sh.server_rules(MESH)["embed"] == ("data",)


def test_multi_pod_fsdp_axes():
    r = sh.server_rules(MESH3)
    assert r["embed"] == ("pod", "data")
    assert r["batch"] == ("pod", "data")


def test_ddp_profile_no_layer_tp():
    r = sh.server_rules(MESH, profile="ddp")
    assert r["ff"] == () and r["heads"] == ()
    assert r["vocab"] == ("model",)
    assert r["batch"] == ("data", "model")

