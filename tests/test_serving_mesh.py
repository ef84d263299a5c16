"""Mesh-sharded fleet estimator serving (repro.sim.serving).

Pins the two load-bearing properties of the serving subsystem on the
host's virtual-device mesh: (1) the sharded per-period program is
numerically interchangeable with the unsharded ``predict`` path
(allclose), and (2) at lowering level the UE batch axis is *actually*
sharded over the mesh's data axis, not silently replicated. Plus the EP
mesh variant: the reserved ``experts`` logical axis finally resolves to
a physical ``expert`` axis.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.channel import scenarios as sc
from repro.dist import sharding as sh
from repro.estimator.model import EstimatorConfig, init_estimator
from repro.launch.mesh import make_host_mesh
from repro.sim import estimate_fleet, make_serving_mesh
from repro.sim.serving import ServingMesh, serving_program

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs >= 8 (virtual) devices")

N_SC_TEST = 16


def tiny_estimator(seed: int = 0):
    e = EstimatorConfig(n_sc=N_SC_TEST, lstm_hidden=8, hidden=8)
    return e, init_estimator(e, jax.random.PRNGKey(seed))


def episode(n: int, T: int = 3, seed: int = 5):
    rng = np.random.default_rng(seed)
    names = np.asarray(sc.SCENARIOS)[np.arange(n) % len(sc.SCENARIOS)]
    return sc.gen_episode_batch(names, T, rng, n_sc=N_SC_TEST)


# ------------------------------------------------------------- equivalence
@multi_device
def test_sharded_matches_unsharded():
    """Mesh-sharded estimate_fleet == unsharded path (allclose), with the
    batch evenly split over an 8-way data axis."""
    e, params = tiny_estimator()
    ep = episode(8)
    base = estimate_fleet(ep, (e, params))
    shd = estimate_fleet(ep, (e, params), serving=make_serving_mesh("8x1"))
    assert shd.shape == base.shape == (8, 3)
    np.testing.assert_allclose(shd, base, rtol=1e-5, atol=1e-4)


@multi_device
def test_sharded_uneven_batch_falls_back():
    """A fleet size not divisible by the data axis replicates (the
    Ruleset divisibility fallback) instead of erroring, and still
    matches."""
    e, params = tiny_estimator()
    ep = episode(6)
    base = estimate_fleet(ep, (e, params))
    shd = estimate_fleet(ep, (e, params), serving=make_serving_mesh("4x2"))
    np.testing.assert_allclose(shd, base, rtol=1e-5, atol=1e-4)


@multi_device
def test_simulate_fleet_composes_with_serving():
    """The engine hook: simulate_fleet(estimator=..., serving=...) runs the
    sharded estimator under the controller scan and feeds controllers the
    same estimates as the unsharded run."""
    from repro.core.controller import ControllerConfig
    from repro.models.vgg import FULL, vgg_split_profile
    from repro.core.pso import LookupTable
    from repro.sim import simulate_fleet

    e, params = tiny_estimator()
    ep = episode(8, T=4)
    prof = vgg_split_profile(FULL)
    table = LookupTable(ue_name="t", table=np.full(41, 3, np.int32),
                        tp_min_mbps=np.zeros(len(prof.data_bytes)),
                        feasible_prefilter=np.ones(len(prof.data_bytes),
                                                   bool))
    cfg = ControllerConfig(0.5, 2, 3)
    base = simulate_fleet(ep, table, prof, cfg, estimator=(e, params))
    shd = simulate_fleet(ep, table, prof, cfg, estimator=(e, params),
                         serving=make_serving_mesh("8x1"))
    np.testing.assert_allclose(shd.est_tp, base.est_tp, rtol=1e-5, atol=1e-4)
    assert shd.splits.shape == base.splits.shape == (8, 4)


# ---------------------------------------------------------------- lowering
@multi_device
def test_lowering_shards_ue_batch_axis():
    """The per-period program's lowering constrains dim 0 of the batch
    inputs to the 8-way data axis: the UE batch axis is actually sharded."""
    e, params = tiny_estimator()
    serving = make_serving_mesh("8x1")
    assert dict(serving.mesh.shape) == {"data": 8, "model": 1}
    fn = serving_program(e, serving)
    n = 8
    pabs = jax.eval_shape(lambda: params)
    lowered = fn.lower(
        pabs,
        jax.ShapeDtypeStruct((n, e.window, e.n_kpms), jnp.float32),
        jax.ShapeDtypeStruct((n, 2, e.n_sc, e.n_sym), jnp.float32),
        jax.ShapeDtypeStruct((n,), jnp.float32))
    text = lowered.as_text()
    # iq is rank 4, kpms rank 3; both must carry a Shardy constraint that
    # puts dim 0 on the 8-way data axis and replicates the rest
    assert ('[{"data"}, {}, {}, {}]> : tensor<8x2x' in text), (
        "iq batch dim not sharded")
    assert ('[{"data"}, {}, {}]> : tensor<8x30x' in text), (
        "kpms batch dim not sharded")


@multi_device
def test_put_commits_batch_sharding():
    """dist.sharding.put places a host array with the batch rule's
    NamedSharding (and is identity outside a ruleset)."""
    x = jnp.ones((8, 4))
    assert sh.put(x, ("batch", None)) is x  # no active ruleset
    serving = make_serving_mesh("8x1")
    with sh.use_rules(serving.mesh):
        y = sh.put(x, ("batch", None))
    assert y.sharding.spec == P("data", None)


# ----------------------------------------------------------------- EP mesh
@multi_device
def test_ep_host_mesh_carries_expert_axis():
    """make_host_mesh(expert=) yields a (data, expert, model) mesh on which
    the 'experts' logical axis resolves — the first mesh to carry it."""
    mesh = make_host_mesh(2, 2, expert=2)
    assert dict(mesh.shape) == {"data": 2, "expert": 2, "model": 2}
    with sh.use_rules(mesh) as rs:
        assert rs.spec(("experts", "ff", "embed"), (4, 8, 16)) == P(
            "expert", "model", None)
        assert rs.axis_size("experts") == 2
        w = sh.put(jnp.ones((4, 8, 16)), ("experts", "ff", None))
    assert w.sharding.spec == P("expert", "model", None)


def test_ep_axis_absent_on_2d_mesh_falls_back():
    """On a plain (data, model) mesh the experts rule still silently
    replicates — the PR-1 fallback contract is unchanged."""
    mesh = make_host_mesh(2, 2)
    with sh.use_rules(mesh) as rs:
        assert rs.spec(("experts", "ff"), (4, 8))[0] is None
        assert rs.axis_size("experts") == 1


def test_make_host_mesh_expert_clamps():
    """expert requests clamp like data/model: a 2-axis mesh comes back
    when the clamped expert size is 1."""
    mesh = make_host_mesh(len(jax.devices()), 1, expert=1)
    assert "expert" not in mesh.shape


# ------------------------------------------------------------- mesh parsing
@multi_device
def test_make_serving_mesh_specs():
    s = make_serving_mesh("4x2")
    assert dict(s.mesh.shape) == {"data": 4, "model": 2}
    assert s.n_chips == 8 and s.describe() == "data=4,model=2"
    s3 = make_serving_mesh("2x2x2")
    assert dict(s3.mesh.shape) == {"data": 2, "expert": 2, "model": 2}
    with pytest.raises(ValueError):
        make_serving_mesh("2x2x2x2")


def test_serving_mesh_is_cache_key():
    """ServingMesh + EstimatorConfig key the program cache: same deployment
    -> same compiled program object."""
    e, _ = tiny_estimator()
    s1 = make_serving_mesh("1x1")
    s2 = make_serving_mesh("1x1")
    assert serving_program(e, s1) is serving_program(e, s2)
