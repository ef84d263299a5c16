"""Mesh-sharded fleet estimator serving (repro.sim.serving).

Pins the two load-bearing properties of the serving subsystem on the
host's virtual-device mesh: (1) the sharded per-period program is
numerically interchangeable with the unsharded ``predict`` path
(allclose), and (2) at lowering level the UE batch axis is *actually*
sharded over the mesh's data axis, not silently replicated. Plus the EP
mesh variant: the reserved ``experts`` logical axis finally resolves to
a physical ``expert`` axis.
"""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.channel import scenarios as sc
from repro.core.pso import TP_CLIP_MBPS
from repro.dist import sharding as sh
from repro.estimator.model import EstimatorConfig, init_estimator
from repro.launch.mesh import make_host_mesh
from repro.sim import estimate_fleet, make_serving_mesh
from repro.sim import serving as serving_mod
from repro.sim import telemetry as tel
from repro.sim.serving import ServingMesh, replicate_params, serving_program

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


# --------------------------------------------------------- input placement
def _two_step_estimate(e, params, ep, serving):
    """The mesh path as it placed inputs before: each host array committed
    whole to the default device (``jnp.asarray``), then resharded onto
    the ``batch`` sharding (``dist.sharding.put``)."""
    fn = serving_program(e, serving)
    wins = ep.kpm_windows(normalize=True).astype(np.float32)
    params_r = replicate_params(serving, params)
    est = np.empty((ep.n_ues, ep.n_steps))
    with sh.use_rules(serving.mesh, serving.rule_overrides()):
        alloc_d = sh.put(jnp.asarray(ep.alloc_ratio.astype(np.float32),
                                     jnp.float32), ("batch",))
        for t in range(ep.n_steps):
            kpms_t = sh.put(jnp.asarray(wins[:, t]), ("batch", None, None))
            iq_t = sh.put(jnp.asarray(ep.iq[:, t], jnp.float32),
                          ("batch", None, None, None))
            est[:, t] = np.asarray(fn(params_r, kpms_t, iq_t, alloc_d))
    return np.clip(est, *TP_CLIP_MBPS)


def _recorded_estimate(monkeypatch, e, params, ep, serving):
    """estimate_fleet on the mesh, with the arguments handed to
    ``fwd_sharded`` and every host array ``jnp.asarray`` or
    ``jax.device_put`` was given on the way."""
    real_fn = serving_program(e, serving)
    args, staged, put = [], [], []

    def fwd(p, *xs):
        args.append(xs)
        return real_fn(p, *xs)

    real_asarray, real_put = jnp.asarray, jax.device_put

    def asarray(x, *a, **k):
        if isinstance(x, np.ndarray):
            staged.append(x.shape)
        return real_asarray(x, *a, **k)

    def device_put(x, *a, **k):
        if isinstance(x, np.ndarray):
            put.append(x.shape)
        return real_put(x, *a, **k)

    monkeypatch.setattr(serving_mod, "serving_program", lambda *a: fwd)
    monkeypatch.setattr(jnp, "asarray", asarray)
    monkeypatch.setattr(jax, "device_put", device_put)
    est = estimate_fleet(ep, (e, params), serving=serving)
    monkeypatch.undo()
    return est, args, staged, put


@multi_device
def test_host_iq_never_staged_whole_on_one_device(monkeypatch):
    """On a 4x1 mesh each period's host IQ (and KPM window, and the alloc
    ratio) goes to ``jax.device_put`` with its batch sharding; none is
    committed whole to one device by ``jnp.asarray`` first."""
    e, params = tiny_estimator()
    ep = episode(8)
    serving = make_serving_mesh("4x1")
    _, args, staged, put = _recorded_estimate(monkeypatch, e, params, ep,
                                              serving)
    assert staged == []
    iq_shape = (8, 2, N_SC_TEST, ep.iq.shape[-1])
    assert put.count(iq_shape) == ep.n_steps
    assert put.count((8,)) == 1  # the alloc ratio, once
    assert len(args) == ep.n_steps


@multi_device
def test_fwd_sharded_args_carry_batch_sharding(monkeypatch):
    """Every array handed to ``fwd_sharded`` has the ``batch``
    NamedSharding on the 4-way data axis, N/4 rows per addressable
    shard."""
    e, params = tiny_estimator()
    ep = episode(8)
    serving = make_serving_mesh("4x1")
    _, args, _, _ = _recorded_estimate(monkeypatch, e, params, ep, serving)
    for xs in args:
        for x in xs:
            assert isinstance(x.sharding, jax.sharding.NamedSharding)
            assert x.sharding.mesh == serving.mesh
            assert x.sharding.spec == P("data", *(None,) * (x.ndim - 1))
            shards = x.addressable_shards
            assert len(shards) == 4
            assert all(s.data.shape[0] == 8 // 4 for s in shards)


@pytest.mark.parametrize("spec", [
    pytest.param("4x1", marks=multi_device), "1x1"])
def test_direct_placement_matches_two_step(spec, monkeypatch):
    """The estimates equal the old two-step placement's bit for bit; on a
    one-device mesh ``fwd_sharded`` still gets device arrays, never bare
    NumPy."""
    e, params = tiny_estimator()
    ep = episode(8)
    serving = make_serving_mesh(spec)
    est, args, _, _ = _recorded_estimate(monkeypatch, e, params, ep,
                                         serving)
    np.testing.assert_array_equal(
        est, _two_step_estimate(e, params, ep, serving))
    assert all(isinstance(x, jax.Array) for xs in args for x in xs)


@pytest.mark.parametrize("spec", [
    pytest.param("4x1", marks=multi_device), "1x1", None])
def test_ingest_put_with_axes(spec):
    """``put`` with axes: under a multi-device ruleset the host array is
    split over the batch axis (``devices`` counts the shards, ``nbytes``
    the global size); on one device or with no ruleset it is
    ``jnp.asarray``."""
    x = np.arange(8 * 3, dtype=np.float64).reshape(8, 3)
    with contextlib.ExitStack() as stack:
        if spec is not None:
            stack.enter_context(sh.use_rules(make_serving_mesh(spec).mesh))
        with tel.ingest() as put:
            y = put(x, axes=("batch", None))
    n = 4 if spec == "4x1" else 1
    assert isinstance(y, jax.Array) and y.dtype == np.float32
    np.testing.assert_array_equal(np.asarray(y), x.astype(np.float32))
    assert put.nbytes == x.size * 4 and put.devices == n
    assert len({s.device for s in y.addressable_shards}) == n
    if n == 1:
        assert y.sharding.is_equivalent_to(jnp.asarray(x).sharding, 2)


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
