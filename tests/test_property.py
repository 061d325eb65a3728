"""Property-based tests (hypothesis) on system invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import Orchestrator, TaskRequest
from repro.core.descriptors import shared_key_ratio
from repro.core.matcher import Matcher
from repro.core.telemetry import RuntimeSnapshot
from repro.models.common import rmsnorm, layernorm, rope
from repro.roofline.analysis import PEAKS, roofline_terms
from repro.substrates import MemristiveAdapter

jax.config.update("jax_platforms", "cpu")


@settings(max_examples=25, deadline=None)
@given(drift=st.floats(0.0, 0.49), drift_hi=st.floats(0.5, 1.0))
def test_matcher_score_monotone_in_drift(drift, drift_hi):
    """More drift must never raise a backend's score (Eq. 1 D-term)."""
    orch = Orchestrator()
    orch.register(MemristiveAdapter())
    task = TaskRequest(function="inference", input_modality="vector",
                       output_modality="vector")
    m = orch.matcher

    def score_at(d):
        orch.bus.update_snapshot(RuntimeSnapshot("memristive-local",
                                                 drift_score=d))
        c = m.score(orch.registry.get("memristive-local"), task)
        return c.score if c.admissible else float("-inf")

    assert score_at(drift) >= score_at(drift_hi)


@settings(max_examples=20, deadline=None)
@given(st.lists(st.dictionaries(st.sampled_from("abcdef"), st.integers(),
                                min_size=1, max_size=6), min_size=1,
                max_size=5))
def test_shared_key_ratio_bounds(dicts):
    r = shared_key_ratio(dicts)
    assert 0.0 <= r <= 1.0
    if all(set(d) == set(dicts[0]) for d in dicts):
        assert r == 1.0


@settings(max_examples=20, deadline=None)
@given(flops=st.floats(1e6, 1e18), byts=st.floats(1e3, 1e15),
       coll=st.floats(0, 1e14))
def test_roofline_terms_invariants(flops, byts, coll):
    for hw in PEAKS.values():
        t = roofline_terms(flops, byts, coll, hw)
        assert t["step_time_lb_s"] == pytest.approx(
            max(t["compute_s"], t["memory_s"], t["collective_s"]))
        assert 0.0 <= t["roofline_fraction"] <= 1.0 + 1e-9
        assert t["dominant"] in ("compute", "memory", "collective")
        # the dominant term is the bound
        assert t[t["dominant"] + "_s"] == pytest.approx(t["step_time_lb_s"])


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 4), st.integers(2, 32), st.integers(2, 64))
def test_rmsnorm_scale_invariance_property(b, s, d):
    """rmsnorm(αx) == rmsnorm(x) for α>0 (scale invariance)."""
    rng = np.random.default_rng(b * 1000 + s * 10 + d)
    x = jnp.asarray(rng.normal(size=(b, s, d)), jnp.float32)
    w = jnp.zeros((d,), jnp.float32)
    y1 = rmsnorm(x, w)
    y2 = rmsnorm(3.7 * x, w)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-4,
                               atol=1e-5)


@settings(max_examples=15, deadline=None)
@given(st.integers(2, 16), st.integers(8, 64))
def test_rope_preserves_norm_property(s, hd):
    hd = hd - hd % 2
    rng = np.random.default_rng(s * 100 + hd)
    x = jnp.asarray(rng.normal(size=(1, s, 2, hd)), jnp.float32)
    pos = jnp.arange(s, dtype=jnp.int32)
    y = rope(x, pos)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(x), axis=-1),
                               np.linalg.norm(np.asarray(y), axis=-1),
                               rtol=1e-4)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 200))
def test_data_pipeline_deterministic_property(seed, step):
    from repro.training.data import SyntheticTokenDataset

    d1 = SyntheticTokenDataset(997, 8, 2, seed=seed)
    d2 = SyntheticTokenDataset(997, 8, 2, seed=seed)
    b1, b2 = d1.batch_at(step), d2.batch_at(step)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    # disjoint host shards differ
    d3 = SyntheticTokenDataset(997, 8, 2, seed=seed, host_id=1, num_hosts=2)
    assert not np.array_equal(d3.batch_at(step)["tokens"], b1["tokens"])


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 6), st.integers(1, 4))
def test_checkpoint_roundtrip_property(depth, width):
    import tempfile
    from repro.training.checkpoint import CheckpointManager

    rng = np.random.default_rng(depth * 7 + width)
    tree = {}
    node = tree
    for i in range(depth):
        node[f"level{i}"] = {"w": rng.normal(size=(width, width)).astype(
            np.float32)}
        node = node[f"level{i}"]
    with tempfile.TemporaryDirectory() as td:
        cm = CheckpointManager(td)
        cm.save(1, tree)
        restored, meta = cm.restore(tree)
        for (pa, a), (pb, b) in zip(
                jax.tree_util.tree_flatten_with_path(tree)[0],
                jax.tree_util.tree_flatten_with_path(restored)[0]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
