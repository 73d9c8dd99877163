"""Client-sharded distributed execution subsystem.

Device-count-agnostic: the array-level plan/aggregation/shard_map tests
run on whatever devices exist (a 1-device mesh included).  The
trainer-level shard_map tests and the end-to-end history gates need a
multi-device mesh and skip on a single device — run the full suite
with:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
        python -m pytest -q tests/test_distributed.py

(conftest skips every other module under a forced device count; the CI
``distributed-8dev`` job runs exactly this invocation.)
"""


import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.config import get_arch
from repro.config.base import FLConfig
from repro.core.aggregation import (staleness_weighted_merge,
                                    weighted_average_stacked)
from repro.core.baselines import (run_fedasync, run_fedasync_sequential,
                                  run_fedavg)
from repro.core.engine import BatchedClientEngine, make_engine
from repro.distributed import (ClientShardingPlan, ensure_host_device_count,
                               forced_host_device_count, make_client_mesh,
                               shard_cohort_train, sharded_aggregate,
                               sharded_staleness_merge)
from repro.distributed.engine import ShardedClientEngine
from repro.fl.client import CNNTrainer
from repro.fl.network import WirelessNetwork
from repro.kernels import fedagg_pytree

N_DEV = len(jax.devices())
multi_device = pytest.mark.skipif(
    N_DEV < 2,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count>=2")

_TRAINER_CACHE = {}


def _setup(rounds=2, n_clients=8, seed=0, lr=0.003, tau=2):
    fl = FLConfig(n_clients=n_clients, n_tiers=4, tau=tau, rounds=rounds,
                  mu=0.0, primary_frac=0.7, seed=seed, lr=lr)
    net = WirelessNetwork(fl.n_clients, fl.tier_delay_means, fl.delay_std,
                          fl.mu, fl.failure_delay, fl.seed)
    key = (n_clients, seed, lr)
    if key not in _TRAINER_CACHE:
        _TRAINER_CACHE[key] = CNNTrainer(get_arch("cnn-mnist").reduced(),
                                         fl, "mnist", scale=0.01)
    return _TRAINER_CACHE[key], net, fl


def _stacked_tree(n, seed=0):
    """Mixed-dtype stacked update pytree: 3-d f32, bf16 matrix, scalar."""
    rng = np.random.default_rng(seed)
    return {
        "f32": jnp.asarray(rng.normal(size=(n, 5, 3)).astype(np.float32)),
        "bf16": jnp.asarray(rng.normal(size=(n, 7)).astype(np.float32)
                            ).astype(jnp.bfloat16),
        "scalar": jnp.asarray(rng.normal(size=(n,)).astype(np.float32)),
    }


def _assert_tree_close(a, b, rtol=1e-5, atol=1e-5, bf16_tol=2e-2):
    for k in b:
        tol = dict(rtol=bf16_tol, atol=bf16_tol) if "bf16" in k \
            else dict(rtol=rtol, atol=atol)
        np.testing.assert_allclose(np.asarray(a[k], np.float32),
                                   np.asarray(b[k], np.float32), **tol)


# ---------------------------------------------------------------------------
# XLA_FLAGS plumbing (hostdevices)
# ---------------------------------------------------------------------------

def test_ensure_host_device_count_appends_not_clobbers():
    env = {"XLA_FLAGS": "--xla_cpu_enable_fast_math=false"}
    assert ensure_host_device_count(8, env) == 8
    assert env["XLA_FLAGS"] == ("--xla_cpu_enable_fast_math=false "
                                "--xla_force_host_platform_device_count=8")


def test_ensure_host_device_count_existing_flag_wins():
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    assert ensure_host_device_count(16, env) == 4
    assert env["XLA_FLAGS"] == "--xla_force_host_platform_device_count=4"
    assert forced_host_device_count(env) == 4


def test_ensure_host_device_count_empty_env():
    env = {}
    assert ensure_host_device_count(2, env) == 2
    assert env["XLA_FLAGS"] == "--xla_force_host_platform_device_count=2"
    with pytest.raises(ValueError):
        ensure_host_device_count(0, {})


def test_forced_host_device_count_absent():
    assert forced_host_device_count({"XLA_FLAGS": "--foo=1"}) is None
    assert forced_host_device_count({}) is None


# ---------------------------------------------------------------------------
# mesh factory
# ---------------------------------------------------------------------------

def test_make_client_mesh_spans_all_devices():
    mesh = make_client_mesh()
    assert mesh.axis_names == ("clients",)
    assert int(mesh.size) == N_DEV


def test_make_client_mesh_subset_and_clamp():
    assert int(make_client_mesh(1).size) == 1
    assert int(make_client_mesh(N_DEV).size) == N_DEV
    with pytest.raises(ValueError, match="only"):   # never clamped
        make_client_mesh(N_DEV + 1)
    with pytest.raises(ValueError):
        make_client_mesh(0)


def test_make_client_mesh_composes_with_launch_factory():
    from repro.launch.mesh import make_client_mesh as launch_make
    mesh = launch_make(devices=make_client_mesh().devices.flatten())
    assert mesh.axis_names == ("clients",)
    assert int(mesh.size) == N_DEV


# ---------------------------------------------------------------------------
# sharding plan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,d,pow2,expect", [
    (3, 8, False, 8),       # N smaller than the mesh
    (12, 8, False, 16),     # N not divisible by the mesh
    (16, 8, False, 16),     # exact multiple: no padding
    (3, 8, True, 8),        # pow2 then mesh multiple
    (5, 4, True, 8),
    (6, 1, True, 8),        # 1-device mesh: pure pow2 convention
    (7, 3, False, 9),       # non-pow2 mesh still lands on a multiple
])
def test_plan_padding_math(n, d, pow2, expect):
    plan = ClientShardingPlan.for_cohort(n, d, pow2=pow2)
    assert plan.padded_n == expect
    assert plan.padded_n % d == 0
    assert plan.pad_rows == expect - n


def test_plan_rejects_empty_cohort():
    with pytest.raises(ValueError):
        ClientShardingPlan.for_cohort(0, 4)


def test_plan_pad_unpad_roundtrip_edge_and_zero():
    tree = _stacked_tree(5)
    plan = ClientShardingPlan.for_cohort(5, 4)
    for mode in ("edge", "zero"):
        padded = plan.pad_stacked(tree, mode=mode)
        assert {l.shape[0] for l in jax.tree_util.tree_leaves(padded)} == {8}
        back = plan.unpad(padded)
        for k in tree:
            assert back[k].dtype == tree[k].dtype
            np.testing.assert_array_equal(
                np.asarray(back[k], np.float32),
                np.asarray(tree[k], np.float32))
    edge = plan.pad_stacked(tree, mode="edge")
    np.testing.assert_array_equal(np.asarray(edge["f32"][-1]),
                                  np.asarray(tree["f32"][-1]))
    zero = plan.pad_stacked(tree, mode="zero")
    assert float(jnp.abs(zero["f32"][5:]).sum()) == 0.0
    w = plan.pad_weights(np.ones(5, np.float32))
    assert w.shape == (8,)
    assert float(w[5:].sum()) == 0.0
    with pytest.raises(ValueError):
        plan.pad_stacked(tree, mode="wat")


# ---------------------------------------------------------------------------
# sharded aggregation parity (uneven cohorts, mixed dtypes, stragglers)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [3, 5, 12, 16])
def test_sharded_aggregate_matches_reference(n):
    """N < mesh, N not divisible by mesh, N a multiple — all must match
    the single-device reduction within dtype tolerance."""
    mesh = make_client_mesh()
    tree = _stacked_tree(n, seed=n)
    rng = np.random.default_rng(n + 1)
    w = rng.uniform(0.5, 2.0, n).astype(np.float32)
    w[0] = 0.0                                 # masked straggler row
    out = sharded_aggregate(mesh, tree, w)
    ref = weighted_average_stacked(tree, w)
    _assert_tree_close(out, ref)


def test_sharded_aggregate_nonuniform_alphas():
    mesh = make_client_mesh()
    n = 11
    tree = _stacked_tree(n, seed=2)
    rng = np.random.default_rng(3)
    w = rng.uniform(0.5, 2.0, n).astype(np.float32)
    alphas = (0.6 * (np.arange(n) + 1.0) ** -0.5).astype(np.float32)
    alphas[4] = 0.0                            # zero-alpha straggler
    out = sharded_aggregate(mesh, tree, w, alphas=alphas)
    ref = weighted_average_stacked(tree, w, alphas=alphas)
    _assert_tree_close(out, ref)


def test_sharded_aggregate_zero_rows_masked_even_nonfinite():
    mesh = make_client_mesh()
    tree = {"w": jnp.asarray([[1.0, 2.0], [np.nan, np.inf], [3.0, 4.0]],
                             jnp.float32)}
    out = sharded_aggregate(mesh, tree, [1.0, 0.0, 1.0])
    np.testing.assert_allclose(np.asarray(out["w"]), [2.0, 3.0], rtol=1e-6)


def test_sharded_aggregate_all_masked_is_zeros():
    mesh = make_client_mesh()
    out = sharded_aggregate(mesh, {"w": jnp.ones((4, 9))}, np.zeros(4))
    np.testing.assert_allclose(np.asarray(out["w"]), 0.0, atol=1e-7)


def test_sharded_aggregate_matches_pallas_fedagg():
    mesh = make_client_mesh()
    n = 6
    tree = _stacked_tree(n, seed=5)
    w = np.asarray([1.0, 2.0, 0.0, 3.0, 0.5, 1.5], np.float32)
    out = sharded_aggregate(mesh, tree, w)
    ref = fedagg_pytree(tree, jnp.asarray(w), interpret=True)
    _assert_tree_close(out, ref)


def test_sharded_aggregate_rejects_length_mismatch():
    mesh = make_client_mesh()
    with pytest.raises(ValueError):
        sharded_aggregate(mesh, {"w": jnp.ones((4, 2))}, np.ones(3))


def test_sharded_staleness_merge_matches_reference():
    mesh = make_client_mesh()
    n = 7
    stacked = _stacked_tree(n, seed=8)
    g = jax.tree_util.tree_map(lambda l: l[0] * 0.5, stacked)
    alphas = (0.6 * (np.arange(n, dtype=np.float64) + 1.0) ** -0.5)
    alphas[2] = 0.0                            # carried straggler: no-op row
    out = sharded_staleness_merge(mesh, g, stacked, alphas)
    ref = staleness_weighted_merge(g, stacked, alphas)
    _assert_tree_close(out, ref)


# ---------------------------------------------------------------------------
# per-shard Pallas fedagg dispatch (interpret mode inside shard_map)
# ---------------------------------------------------------------------------

def test_sharded_aggregate_kernel_dispatch_matches_jnp():
    """use_kernel=True reduces each shard's rows through the
    fedagg_partial Pallas kernel (interpret on CPU); the psum combine
    and masking semantics are unchanged."""
    mesh = make_client_mesh()
    n = 9
    tree = _stacked_tree(n, seed=11)
    rng = np.random.default_rng(12)
    w = rng.uniform(0.5, 2.0, n).astype(np.float32)
    w[3] = 0.0                                 # masked straggler row
    out_k = sharded_aggregate(mesh, tree, w, use_kernel=True)
    out_j = sharded_aggregate(mesh, tree, w)
    _assert_tree_close(out_k, out_j)
    ref = weighted_average_stacked(tree, w)
    _assert_tree_close(out_k, ref)


def test_sharded_aggregate_kernel_all_masked_fallback():
    mesh = make_client_mesh()
    fallback = {"w": jnp.asarray([5.0, 6.0], jnp.float32)}
    out = sharded_aggregate(mesh, {"w": jnp.full((4, 2), np.nan)},
                            np.zeros(4), fallback=fallback,
                            use_kernel=True)
    np.testing.assert_array_equal(np.asarray(out["w"]),
                                  np.asarray(fallback["w"]))


def test_sharded_staleness_merge_kernel_dispatch_matches_reference():
    """The sharded kernel-merge parity case: per-shard fedagg_partial
    partial sums + one psum must match the single-device folded merge
    within float tolerance (runs on whatever mesh exists — the
    forced-8-host-device CI job included)."""
    mesh = make_client_mesh()
    n = 10
    stacked = _stacked_tree(n, seed=13)
    g = jax.tree_util.tree_map(lambda l: l[0] * 0.5, stacked)
    alphas = (0.6 * (np.arange(n, dtype=np.float64) + 1.0) ** -0.5)
    alphas[4] = 0.0                            # carried straggler: no-op row
    out_k = sharded_staleness_merge(mesh, g, stacked, alphas,
                                    use_kernel=True)
    ref = staleness_weighted_merge(g, stacked, alphas)
    _assert_tree_close(out_k, ref)
    out_j = sharded_staleness_merge(mesh, g, stacked, alphas)
    _assert_tree_close(out_k, out_j)


# ---------------------------------------------------------------------------
# shard_cohort_train mechanics (pure functions, no trainer)
# ---------------------------------------------------------------------------

def test_shard_cohort_train_elementwise_parity_uneven():
    mesh = make_client_mesh()

    def train(starts, x):
        return jax.tree_util.tree_map(
            lambda l: l + x[:, :1] ** 2, starts)

    run = shard_cohort_train(mesh, train, replicated=0)
    for n in (2, 5, 16):                       # < mesh, uneven, multiple
        starts = {"w": jnp.arange(float(n * 3)).reshape(n, 3)}
        x = jnp.arange(float(n * 4)).reshape(n, 4)
        out = run(starts, x)
        ref = train(starts, x)
        assert out["w"].shape == (n, 3)
        np.testing.assert_allclose(np.asarray(out["w"]),
                                   np.asarray(ref["w"]), rtol=1e-6)


def test_shard_cohort_train_replicated_leading_arg():
    mesh = make_client_mesh()

    def train(params, x):
        return {"w": x * params["scale"]}

    run = shard_cohort_train(mesh, train, replicated=1)
    x = jnp.arange(float(N_DEV * 2 + 1)).reshape(-1, 1)   # uneven rows
    out = run({"scale": jnp.asarray(3.0)}, x)
    np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(x) * 3.0)


def test_shard_cohort_train_requires_sharded_arg():
    mesh = make_client_mesh()
    run = shard_cohort_train(mesh, lambda p: p, replicated=1)
    with pytest.raises(ValueError):
        run({"w": jnp.ones(3)})


# ---------------------------------------------------------------------------
# engine selection
# ---------------------------------------------------------------------------

class _FakeLoopTrainer:
    class cfg:
        arch_id = "fake"

    def init_params(self, seed=0):
        return {"w": jnp.zeros(4, jnp.float32)}

    def local_train(self, params, client_id, rnd_seed):
        return {"w": params["w"] + 1.0 + client_id}, 10 + client_id


def test_make_engine_one_device_mesh_is_plain_engine():
    """The documented single-device guarantee: a 1-device mesh selects
    the existing engine, so histories are bit-identical by
    construction."""
    eng = make_engine(_FakeLoopTrainer(), mesh=make_client_mesh(1))
    assert type(eng) is BatchedClientEngine


def test_make_engine_looped_plus_mesh_rejected_or_passthrough():
    if N_DEV > 1:
        with pytest.raises(ValueError):
            make_engine(_FakeLoopTrainer(), engine="looped",
                        mesh=make_client_mesh())
    eng = make_engine(_FakeLoopTrainer(), engine="looped",
                      mesh=make_client_mesh(1))
    assert eng.force_looped


@multi_device
def test_sharded_engine_kernel_agg_dispatches_per_shard():
    """The sharded engine no longer discards use_kernel_agg: merges run
    the per-shard fedagg_partial dispatch inside the psum reduction and
    match the plain kernel engine."""
    eng = make_engine(_FakeLoopTrainer(), mesh=make_client_mesh(),
                      use_kernel_agg=True)
    assert isinstance(eng, ShardedClientEngine)
    assert eng.use_kernel_agg
    p = {"w": jnp.zeros(4, jnp.float32)}
    out = eng.train_round(p, [1, 3], rnd_seed=0)
    plain = make_engine(_FakeLoopTrainer(), use_kernel_agg=True)
    ref = plain.train_round(p, [1, 3], rnd_seed=0)
    np.testing.assert_allclose(np.asarray(out["w"]),
                               np.asarray(ref["w"]), rtol=1e-5)


@multi_device
def test_make_engine_multi_device_mesh_is_sharded():
    mesh = make_client_mesh()
    eng = make_engine(_FakeLoopTrainer(), mesh=mesh)
    assert isinstance(eng, ShardedClientEngine)
    assert eng.mesh is mesh
    # pad target composes pow2 with the mesh multiple
    assert eng._pad_target(3) % int(mesh.size) == 0


@multi_device
def test_sharded_engine_loop_only_trainer_falls_back():
    """A trainer without the batched paths (or the wrap hook) keeps the
    looped fallback semantics under a multi-device mesh."""
    eng = make_engine(_FakeLoopTrainer(), mesh=make_client_mesh())
    p = {"w": jnp.zeros(4)}
    out = eng.train_round(p, [1, 3], rnd_seed=0)
    expect = (2.0 * 11 + 4.0 * 13) / 24
    np.testing.assert_allclose(np.asarray(out["w"]),
                               np.full(4, expect, np.float32), rtol=1e-5)


# ---------------------------------------------------------------------------
# trainer-level shard_map parity (forced multi-device CI job)
# ---------------------------------------------------------------------------

@multi_device
def test_cohort16_trains_sharded_and_matches_single_device():
    """The acceptance gate: a 16-client cohort trains under shard_map
    across the client mesh and matches the single-device engine row for
    row; the sharded merge with nonuniform staleness alphas and a
    zero-weight straggler row matches the reference merge."""
    tr, _, fl = _setup(n_clients=16)
    mesh = make_client_mesh()
    sharded = make_engine(tr, mesh=mesh)
    plain = make_engine(tr)
    assert isinstance(sharded, ShardedClientEngine)

    ids = list(range(16))
    seeds = [7 * c + 1 for c in ids]
    starts = [tr.init_params(c % 3) for c in ids]
    s_stacked, s_sizes = sharded.train_cohort(starts, ids, seeds)
    p_stacked, p_sizes = plain.train_cohort(starts, ids, seeds)
    np.testing.assert_array_equal(s_sizes, p_sizes)
    for a, b in zip(jax.tree_util.tree_leaves(s_stacked),
                    jax.tree_util.tree_leaves(p_stacked)):
        assert a.shape[0] == 16
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=5e-5)

    alphas = 0.6 * (np.arange(16, dtype=np.float64) + 1.0) ** -0.5
    alphas[3] = 0.0                            # zero-weight straggler row
    g = tr.init_params(0)
    merged = sharded.merge_staleness(g, s_stacked, alphas)
    ref = plain.merge_staleness(g, p_stacked, alphas)
    for a, b in zip(jax.tree_util.tree_leaves(merged),
                    jax.tree_util.tree_leaves(ref)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-4)


@multi_device
@pytest.mark.parametrize("ids", [[0, 1, 2], [0, 1, 2, 3, 4]])
def test_train_clients_sharded_uneven_cohort_matches(ids):
    """Sync path (replicated args: the shared global params and the
    trainer's two sample tables) with a cohort smaller than the mesh
    and one padded to a pow2 bucket; the tables are replicated over
    the mesh once, not on every round."""
    tr, _, fl = _setup()
    mesh = make_client_mesh()
    sharded = make_engine(tr, mesh=mesh)
    plain = make_engine(tr)
    params = tr.init_params(0)
    for rnd_seed in (1, 2):
        s_stacked, s_sizes = sharded.train_clients(params, ids, rnd_seed)
        p_stacked, p_sizes = plain.train_clients(params, ids, rnd_seed)
        np.testing.assert_array_equal(s_sizes, p_sizes)
        for a, b in zip(jax.tree_util.tree_leaves(s_stacked),
                        jax.tree_util.tree_leaves(p_stacked)):
            assert a.shape[0] == len(ids)
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32), atol=5e-5)
    replicated = NamedSharding(mesh, P())
    assert set(tr._tables) == {None, replicated}
    assert all(t.sharding == replicated for t in tr._tables[replicated])


@multi_device
def test_fedavg_sharded_history_matches_single_device():
    tr, net, fl = _setup()
    hs = run_fedavg(tr, net, fl, mesh=make_client_mesh())
    tr2, net2, fl2 = _setup()
    hp = run_fedavg(tr2, net2, fl2)
    assert hs.rounds == hp.rounds
    np.testing.assert_allclose(hs.times, hp.times, rtol=1e-9)
    np.testing.assert_allclose(hs.accuracy, hp.accuracy, atol=5e-3)


@multi_device
def test_fedasync_window0_gate_holds_with_one_device_mesh():
    """PR 2 regression gate with the distributed path enabled: a
    1-device client mesh must leave run_fedasync(window=0)
    history-identical to the legacy sequential loop."""
    tr, net, fl = _setup()
    hs = run_fedasync_sequential(tr, net, fl, eval_every=3)
    tr2, net2, fl2 = _setup()
    hr = run_fedasync(tr2, net2, fl2, window=0, eval_every=3,
                      mesh=make_client_mesh(1))
    assert hs.rounds == hr.rounds
    assert hs.times == hr.times
    assert hs.accuracy == hr.accuracy
    assert hs.n_selected == hr.n_selected


@multi_device
def test_client_state_store_sharded_matches_plain():
    """The store's row axis shards over the client mesh (rows padded to
    a mesh multiple via ClientShardingPlan); gathers/scatters and the
    fused merge+scatter must match the single-device store within
    float tolerance."""
    from repro.core.aggregation import staleness_merge_coefficients
    from repro.core.state import ClientStateStore
    mesh = make_client_mesh()
    template = {"f32": jnp.asarray(np.arange(15.0, dtype=np.float32)
                                   .reshape(5, 3)),
                "bf16": jnp.asarray(np.arange(7.0, dtype=np.float32)
                                    ).astype(jnp.bfloat16),
                "scalar": jnp.float32(0.5)}
    other = jax.tree_util.tree_map(lambda l: l * 2.0 + 1.0, template)
    plain = ClientStateStore(template, 12)
    shard = ClientStateStore(template, 12, mesh=mesh)
    assert shard.rows % int(mesh.size) == 0 and shard.rows >= 12

    for s in (plain, shard):
        s.scatter_params([3, 5], other)
    for c in (0, 3, 5, 11):
        _assert_tree_close(shard.gather_one(c), plain.gather_one(c),
                           rtol=0, atol=0, bf16_tol=0)

    # stacked updates share the template's structure / per-row shapes
    stacked = {"f32": jnp.broadcast_to(template["f32"], (8, 5, 3)) * 1.1,
               "bf16": (jnp.ones((8, 7), jnp.float32) * 0.3
                        ).astype(jnp.bfloat16),
               "scalar": jnp.arange(8.0, dtype=jnp.float32)}
    alphas = 0.6 * (np.arange(8, dtype=np.float64) + 1.0) ** -0.5
    alphas[2] = 0.0
    coef = staleness_merge_coefficients(alphas)
    ids = list(range(8))
    pp, _ = plain.merge_scatter(ids, stacked, coef, template)
    ps, _ = shard.merge_scatter(ids, stacked, coef, template)
    _assert_tree_close(ps, pp)
    _assert_tree_close(shard.gather_one(4), plain.gather_one(4))


@multi_device
def test_client_state_store_sharded_kernel_merge_matches_plain():
    """The row-sharded store's kernel merge reduces per shard inside
    shard_map (a Pallas kernel cannot be partitioned by XLA on a
    multi-chip mesh) and matches the single-device kernel merge."""
    from repro.core.aggregation import staleness_merge_coefficients
    from repro.core.state import ClientStateStore
    mesh = make_client_mesh()
    template = {"w": jnp.asarray(np.arange(300.0, dtype=np.float32)
                                 .reshape(3, 100) / 300.0),
                "b": jnp.zeros((7,), jnp.float32)}
    plain = ClientStateStore(template, 12)
    shard = ClientStateStore(template, 12, mesh=mesh)
    stacked = jax.tree_util.tree_map(
        lambda l: jnp.stack([l * (1.0 + 0.1 * i) + i for i in range(8)]),
        template)
    alphas = 0.6 * (np.arange(8, dtype=np.float64) + 1.0) ** -0.5
    alphas[5] = 0.0
    coef = staleness_merge_coefficients(alphas)
    ids = list(range(8))
    pp, _ = plain.merge_scatter(ids, stacked, coef, template,
                                use_kernel=True)
    ps, _ = shard.merge_scatter(ids, stacked, coef, template,
                                use_kernel=True)
    _assert_tree_close(ps, pp)
    _assert_tree_close(shard.gather_one(2), plain.gather_one(2))


@multi_device
def test_fedasync_windowed_sharded_matches_single_device():
    """Windowed async cohorts train sharded and merge within tolerance
    of the single-device runtime."""
    tr, net, fl = _setup(seed=1)
    hs = run_fedasync(tr, net, fl, window_secs=20.0, eval_every=4,
                      mesh=make_client_mesh())
    tr2, net2, fl2 = _setup(seed=1)
    hp = run_fedasync(tr2, net2, fl2, window_secs=20.0, eval_every=4)
    assert hs.rounds == hp.rounds
    assert hs.times == hp.times
    assert hs.meta["mean_cohort"] == hp.meta["mean_cohort"]
    np.testing.assert_allclose(hs.accuracy, hp.accuracy, atol=5e-3)


@multi_device
def test_fedasync_windowed_sharded_kernel_store_matches_single_device():
    """Everything at once: client-mesh sharded training, the
    row-sharded store, and the Pallas kernel merge dispatch — within
    tolerance of the plain single-device kernel runtime."""
    tr, net, fl = _setup(seed=1)
    hs = run_fedasync(tr, net, fl, window_secs=20.0, eval_every=4,
                      mesh=make_client_mesh(), use_kernel_agg=True)
    assert hs.meta["store_path"] == "store"
    tr2, net2, fl2 = _setup(seed=1)
    hp = run_fedasync(tr2, net2, fl2, window_secs=20.0, eval_every=4,
                      use_kernel_agg=True)
    assert hs.rounds == hp.rounds
    assert hs.times == hp.times
    assert hs.meta["mean_cohort"] == hp.meta["mean_cohort"]
    np.testing.assert_allclose(hs.accuracy, hp.accuracy, atol=5e-3)
