"""fleet/ — partial participation at population scale (DESIGN.md §3.9).

Covers the cohort-RR walk, the sharded client-state store's gather/scatter
contract (DIANA single shifts AND DIANA-RR slot tables), the per-cohort
stream view, the simulator fleet driver, and the production acceptance
criteria: a cohort == population cohort-RR fleet run bit-matches today's
full-participation wire trajectory (params, shift tables, bits) for
`diana` and `diana_rr` on the 1-pod and 2-pod meshes, and fleet `--resume`
is bit-deterministic.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.pipeline import CohortStream, make_batch_stream
from repro.data.reshuffle import ReshuffleSampler
from repro.fleet import (AsyncFleetRunner, AsyncPlanner, ChaosConfig,
                         CohortSampler, ClientStateStore, FaultyStore,
                         FleetRunner, TransientStoreError)

needs_mesh = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs 8 forced host devices"
)


# ---------------------------------------------------------------------------
# CohortSampler: the client-level RR walk
# ---------------------------------------------------------------------------

def test_cohort_rr_visits_every_client_once_per_fleet_epoch():
    """C=10, m=4: cohorts straddle the fleet-epoch boundary mid-round
    (round 2 takes the last 2 clients of epoch 0 and the first 2 of epoch
    1), yet after any whole number of fleet epochs every client has
    participated exactly that many times."""
    cs = CohortSampler(10, 4, seed=3)
    counts = np.zeros(10, np.int64)
    for r in range(5):  # 5 rounds * 4 = 20 slots = exactly 2 fleet epochs
        cohort = cs.cohort_for_round(r)
        assert cohort.shape == (4,)
        assert (np.diff(cohort) > 0).all(), "sorted, distinct"
        counts[cohort] += 1
    assert (counts == 2).all(), counts
    # the straddling round really mixes two epochs' (effective) orders
    e0, e1 = cs.effective_order(0), cs.effective_order(1)
    straddle = cs.cohort_for_round(2)
    assert set(straddle) == set(e0[8:]) | set(e1[:2])
    # ... and each effective order is still a full permutation (exactly
    # once per epoch even with the head deconflicted against e0's tail)
    assert sorted(e1.tolist()) == list(range(10))
    # closed-form participation counts == replayed counts, mid-epoch too
    for r in range(6):
        replay = np.zeros(10, np.int64)
        for q in range(r):
            replay[cs.cohort_for_round(q)] += 1
        assert np.array_equal(cs.participation_counts(r), replay), r


def test_cohort_straddle_deconfliction():
    """Adjacent epochs' raw permutations are independent, so a straddling
    cohort could draw the same client from epoch e's tail and epoch e+1's
    head — ill-defined for the store scatter. Regression: seed 0 on
    (C=10, m=4) puts client 1 in both; the effective order moves it out of
    the straddling round's reach while keeping exactly-once coverage.
    Sweeps seeds/shapes, and checks cold-cache random access (a resumed
    run's first lookup) matches the sequential walk."""
    raw = CohortSampler(10, 4, seed=0)
    # round 2 takes epoch 0's last 2 slots + epoch 1's first 2: the raw
    # draws collide there (this is the seed the bug reproduced with)
    assert np.intersect1d(raw.epoch_order(0)[8:],
                          raw.epoch_order(1)[:2]).size > 0
    assert (np.diff(raw.cohort_for_round(2)) > 0).all()
    for seed in range(8):
        for C, m in ((10, 4), (7, 3), (13, 5), (9, 2)):
            cs = CohortSampler(C, m, seed=seed)
            rounds = [cs.cohort_for_round(r) for r in range(3 * C // m + 2)]
            for r, co in enumerate(rounds):
                assert (np.diff(co) > 0).all(), (seed, C, m, r, co)
            for e in range(3):
                assert sorted(cs.effective_order(e).tolist()) == \
                    list(range(C)), (seed, C, m, e)
            cold = CohortSampler(C, m, seed=seed)
            r = len(rounds) - 1
            assert np.array_equal(cold.cohort_for_round(r), rounds[r])


def test_cohort_sampler_idempotent_and_stateless():
    cs = CohortSampler(12, 4, seed=7)
    a = [cs.cohort_for_round(r) for r in range(4)]
    b = [CohortSampler(12, 4, seed=7).cohort_for_round(r) for r in range(4)]
    for x, y in zip(a, b):
        assert np.array_equal(x, y)
    assert cs.cursor(0) == (0, 0)
    assert cs.cursor(3) == (1, 0)
    assert cs.cursor(4) == (1, 4)


def test_with_replacement_mode_distinct_within_round():
    cs = CohortSampler(20, 6, mode="with_replacement", seed=1)
    seen = []
    for r in range(10):
        c = cs.cohort_for_round(r)
        assert (np.diff(c) > 0).all(), "distinct ids (scatter well-defined)"
        seen.append(tuple(c))
    assert len(set(seen)) > 1, "i.i.d. across rounds"
    assert np.array_equal(cs.cohort_for_round(3), seen[3])
    # replayed counts drive the resume path for the i.i.d. baseline
    replay = np.zeros(20, np.int64)
    for r in range(7):
        replay[cs.cohort_for_round(r)] += 1
    assert np.array_equal(cs.participation_counts(7), replay)


def test_cohort_sampler_validation():
    with pytest.raises(ValueError):
        CohortSampler(4, 8)
    with pytest.raises(ValueError):
        CohortSampler(4, 2, mode="bogus")


# ---------------------------------------------------------------------------
# ClientStateStore: sharded gather/scatter round-trip
# ---------------------------------------------------------------------------

def _params():
    return {"w": jnp.zeros((3, 5), jnp.float32), "b": jnp.zeros((4,))}


@pytest.mark.parametrize("rule_name,lead", [("single", ()), ("per_slot", (2,))])
def test_store_gather_scatter_roundtrip(rule_name, lead):
    """Gather -> mutate -> scatter -> re-gather is the identity on the
    cohort rows and a no-op on everyone else, across shard boundaries
    (shard_size=3 splits an 11-client population into 4 shards), for both
    the DIANA single-shift and the DIANA-RR slot-table layouts."""
    from repro.core.rules import get_rule

    store = ClientStateStore.create(_params(), 11, get_rule(rule_name),
                                    n_slots=2, shard_size=3)
    cohort = np.array([0, 2, 5, 10])  # hits shards 0, 0, 1, 3
    got = store.gather(cohort)
    assert got["w"].shape == (4,) + lead + (3, 5)
    assert got["b"].shape == (4,) + lead + (4,)
    rng = np.random.default_rng(0)
    upd = jax.tree.map(
        lambda x: rng.normal(size=x.shape).astype(np.float32), got)
    store.scatter(cohort, upd)
    back = store.gather(cohort)
    for k in upd:
        assert np.array_equal(back[k], upd[k]), k
    rest = np.array([1, 3, 4, 6, 7, 8, 9])
    for leaf in jax.tree.leaves(store.gather(rest)):
        assert np.abs(leaf).max() == 0, "untouched clients stay zero"
    # cursors + bit counters ride the same cohort addressing
    store.advance(cohort, 2)
    store.add_bits(cohort, 640.0)
    assert store.cursors(cohort).tolist() == [2] * 4
    assert store.cursors(rest).tolist() == [0] * 7
    assert store.bits[cohort].tolist() == [640.0] * 4


def test_store_memmap_backing(tmp_path):
    from repro.core.rules import get_rule

    store = ClientStateStore.create(_params(), 9, get_rule("single"),
                                    shard_size=4, path=str(tmp_path))
    assert store.num_shards == 3
    cohort = np.array([3, 4, 8])
    upd = store.gather(cohort)
    upd = jax.tree.map(lambda x: x + 1.25, upd)
    store.scatter(cohort, upd)
    got = store.gather(cohort)
    assert np.array_equal(got["w"], upd["w"])
    assert len(list(tmp_path.iterdir())) == 2 * 3  # 2 leaves x 3 shards


def test_store_unwritable_path_fails_readably(tmp_path):
    """--store-path pointing at a non-directory (or an unwritable mount)
    fails up front with an actionable message, not deep inside np.memmap."""
    from repro.core.rules import get_rule

    not_a_dir = tmp_path / "occupied"
    not_a_dir.write_bytes(b"x")
    with pytest.raises(OSError, match="not a writable directory"):
        ClientStateStore.create(_params(), 4, get_rule("single"),
                                path=str(not_a_dir))


def test_store_rejects_bad_cohorts():
    from repro.core.rules import get_rule

    store = ClientStateStore.create(_params(), 8, get_rule("single"),
                                    shard_size=4)
    with pytest.raises(ValueError, match="strictly increasing"):
        store.gather(np.array([2, 1]))
    with pytest.raises(ValueError, match="strictly increasing"):
        store.gather(np.array([1, 1, 2]))
    with pytest.raises(ValueError, match="outside"):
        store.gather(np.array([1, 8]))
    got = store.gather(np.array([0, 1]))
    with pytest.raises(ValueError, match="cohort slice"):
        store.scatter(np.array([0, 1, 2]), got)
    # regression: an UNSORTED cohort with out-of-range ids must get the
    # bounds error NAMING the bad ids, not a misleading sortedness
    # complaint (the old check looked only at cohort[0]/cohort[-1], which
    # both pass for e.g. [9, 2] — then blamed the ordering)
    with pytest.raises(ValueError, match=r"outside \[0, 8\): \[9\]"):
        store.gather(np.array([9, 2]))
    with pytest.raises(ValueError, match=r"\[-3, 11\]"):
        store.gather(np.array([-3, 11]))
    # many offenders: first 8 shown, the rest counted
    with pytest.raises(ValueError, match=r"\(\+2 more\)"):
        store.gather(np.arange(10) + 8)


# ---------------------------------------------------------------------------
# CohortStream: the per-cohort view of the population stream
# ---------------------------------------------------------------------------

def test_cohort_stream_full_participation_matches_batch_stream():
    """cohort == population under cohort-RR: every round samples every
    client in ascending order, so the emitted batches are bitwise the
    full-participation BatchStream's — the stream half of the fleet
    bit-match invariant. Runs across a data-epoch boundary."""
    m, n, b = 4, 3, 2
    data = {"x": np.arange(m * n * b * 5, dtype=np.float32).reshape(
        m, n, b, 5)}
    sampler = ReshuffleSampler(m, n, mode="rr", seed=1)
    with CohortStream(data, sampler, CohortSampler(m, m, seed=0),
                      local_steps=2) as cstream, \
            make_batch_stream(data, sampler, local_steps=2,
                              prefetch=False) as bstream:
        for t in range(2 * n):
            fr = next(cstream)
            assert fr.round == t
            assert np.array_equal(fr.cohort, np.arange(m))
            assert np.array_equal(fr.batch["x"], next(bstream)["x"]), t


def test_cohort_stream_partial_rows_follow_per_client_cursors():
    """Partial participation: a sampled client's rows come from ITS next RR
    position (clients advance only when sampled), modalities stay aligned,
    and a stream rebuilt at `start_round` replays identically."""
    C, n, b, m = 6, 3, 2, 2
    rng = np.random.default_rng(0)
    data = {"x": rng.normal(size=(C, n, b, 4)).astype(np.float32),
            "y": rng.normal(size=(C, n, b)).astype(np.float32)}
    sampler = ReshuffleSampler(C, n, mode="rr", seed=4)
    cohorts = CohortSampler(C, m, seed=9)
    counts = np.zeros(C, np.int64)
    rounds = []
    with CohortStream(data, sampler, cohorts, prefetch=False) as stream:
        for t in range(8):
            fr = next(stream)
            rounds.append(fr)
            for i, c in enumerate(fr.cohort):
                e, pos = divmod(counts[c], n)
                want = sampler.epoch_order(e)[c, pos]
                assert fr.cols[i, 0] == want, (t, c)
                assert np.array_equal(fr.batch["x"][i * b:(i + 1) * b],
                                      data["x"][c, want])
                assert np.array_equal(fr.batch["y"][i * b:(i + 1) * b],
                                      data["y"][c, want])
            counts[fr.cohort] += 1
    with CohortStream(data, sampler, cohorts, prefetch=False,
                      start_round=5) as resumed:
        for t in range(5, 8):
            fr = next(resumed)
            assert np.array_equal(fr.cohort, rounds[t].cohort)
            assert np.array_equal(fr.batch["x"], rounds[t].batch["x"]), t


def test_cohort_stream_prefetch_matches_sync():
    C, n, b, m = 5, 3, 1, 2
    data = {"x": np.arange(C * n * b * 2, dtype=np.float32).reshape(
        C, n, b, 2)}
    sampler = ReshuffleSampler(C, n, seed=2)
    args = (data, sampler, CohortSampler(C, m, seed=1))
    with CohortStream(*args, prefetch=True) as pre, \
            CohortStream(*args, prefetch=False) as sync:
        for _ in range(7):
            a, s = next(pre), next(sync)
            assert a.round == s.round
            assert np.array_equal(a.batch["x"], s.batch["x"])


# ---------------------------------------------------------------------------
# simulator fleet driver (core.algorithms.run_fleet_rounds)
# ---------------------------------------------------------------------------

def _logreg(m, seed=0):
    from repro.data.logreg import make_federated_logreg

    return make_federated_logreg(m=m, n_batches=4, batch=5, d=32, cond=50.0,
                                 seed=seed)


@pytest.mark.parametrize("name", ["q_rr", "diana", "diana_rr"])
def test_run_fleet_rounds_full_participation_matches_epoch_driver(name):
    """cohort == population, exact compression: n fleet rounds ARE one
    `_nonlocal_epoch` scan — params agree with `run_epochs` to float
    noise, and the store's shift tables equal FedState.shifts."""
    from repro.compression.ops import RandK
    from repro.core.algorithms import (
        ALGORITHMS, init_algorithm, make_epoch_fn, run_fleet_rounds)
    from repro.core.rules import get_rule
    from repro.data.pipeline import run_epochs

    prob = _logreg(m=6)
    loss = prob.loss_fn()
    params0 = {"w": jnp.zeros((prob.d,))}
    spec = ALGORITHMS[name]
    rule = get_rule(spec.shift_mode)
    sampler = ReshuffleSampler(prob.m, prob.n, mode="rr_once", seed=2)
    store = ClientStateStore.create(params0, prob.m, rule, n_slots=prob.n,
                                    shard_size=4)
    pf, info = run_fleet_rounds(
        name, loss, RandK(fraction=1.0), gamma=0.05, params=params0,
        data=prob.data, sampler=sampler, store=store,
        cohort_sampler=CohortSampler(prob.m, prob.m, seed=1),
        rounds=2 * prob.n, key=jax.random.PRNGKey(0))
    _, epoch = make_epoch_fn(name, loss, RandK(fraction=1.0), gamma=0.05)
    st = init_algorithm(spec, params0, prob.m, prob.n)
    st = run_epochs(epoch, st, prob.data, sampler, epochs=2,
                    key=jax.random.PRNGKey(0))
    np.testing.assert_allclose(np.asarray(pf["w"]),
                               np.asarray(st.params["w"]), atol=1e-6)
    if rule.has_shifts:
        got = store.gather(np.arange(prob.m))
        np.testing.assert_allclose(np.asarray(got["w"]),
                                   np.asarray(st.shifts["w"]), atol=1e-6)
    assert info["rounds"] == 2 * prob.n
    assert np.array_equal(store.cursor, np.full(prob.m, 2 * prob.n))


def test_run_fleet_rounds_partial_participation_converges():
    """DIANA with a 3-of-12 cohort on heterogeneous logreg: suboptimality
    drops by >10x, bits are charged per participation, and the store's
    cursors equal the closed-form cohort walk."""
    from repro.compression.ops import RandK
    from repro.core.algorithms import run_fleet_rounds
    from repro.core.rules import get_rule

    prob = _logreg(m=12, seed=1)
    params0 = {"w": jnp.zeros((prob.d,))}
    store = ClientStateStore.create(params0, 12, get_rule("single"),
                                    shard_size=5)
    cohorts = CohortSampler(12, 3, seed=7)
    sub0 = prob.suboptimality(params0["w"])
    p, info = run_fleet_rounds(
        "diana", prob.loss_fn(), RandK(fraction=0.5), gamma=0.05,
        params=params0, data=prob.data,
        sampler=ReshuffleSampler(12, 4, mode="rr", seed=3), store=store,
        cohort_sampler=cohorts, rounds=200, key=jax.random.PRNGKey(5))
    assert prob.suboptimality(p["w"]) < 0.1 * sub0
    assert np.array_equal(store.cursor, cohorts.participation_counts(200))
    assert store.bits.sum() == pytest.approx(info["bits"])
    # per-client accounting: bits proportional to participations
    assert np.array_equal(store.bits > 0, store.cursor > 0)


def test_run_fleet_rounds_rejects_local_family():
    from repro.core.algorithms import make_round_fn

    with pytest.raises(ValueError, match="local-family"):
        make_round_fn("q_nastya", lambda p, b: 0.0, gamma=0.1)


# ---------------------------------------------------------------------------
# production acceptance: cohort == population bit-matches the flat wire
# ---------------------------------------------------------------------------

def _tiny_cfg():
    from repro.configs import get_config, reduced

    cfg = reduced(get_config("stablelm-1.6b"), seq=8)
    return dataclasses.replace(cfg, dtype=jnp.float32)


def _fleet_setup(mesh, method, *, n=3, elastic=False, local_steps=1,
                 mean_scale=1.0):
    from repro.core.dist import CompressedAggregation
    from repro.launch import steps
    from repro.launch.mesh import num_clients

    cfg = _tiny_cfg()
    m = num_clients(mesh)
    slotted = method == "diana_rr"
    agg = CompressedAggregation(method=method, wire="shared", fraction=0.5,
                                n_slots=n if slotted else 1,
                                shift_dtype=jnp.float32,
                                mean_scale=mean_scale)
    jitted, abstract, shardings, batch_sh = steps.make_train_step(
        cfg, mesh, agg=agg, lr=0.05, remat=False, seq_shard=False,
        elastic=elastic, local_steps=local_steps)
    return cfg, m, agg, jitted, abstract, shardings, batch_sh


def _population_tokens(cfg, C, n, b, seq, seed=0):
    from repro.data.tokens import synthetic_token_batches

    return {"tokens": np.asarray(synthetic_token_batches(
        vocab=cfg.vocab, seq_len=seq, batch=b, num_batches=n,
        num_clients=C, seed=seed))}


@needs_mesh
@pytest.mark.parametrize("method", ["diana", "diana_rr"])
@pytest.mark.parametrize("mesh_name", ["mesh_4x2", "mesh_2x2x2"])
def test_fleet_full_cohort_bit_matches_flat_wire(method, mesh_name, request):
    """THE acceptance criterion: with C == mesh clients and cohort-RR, the
    fleet path (host store + per-round gather/scatter through
    `with_cohort_shifts`) walks a bitwise-identical trajectory to today's
    full-participation loop — params AND shift tables — on the 1-pod and
    2-pod meshes, and charges exactly the static per-round uplink bits."""
    from repro.core.rules import WIRE_RULES
    from repro.data.pipeline import shared_slots_for_step
    from repro.launch import steps

    mesh = request.getfixturevalue(mesh_name)
    n, b, seq, total = 3, 1, 8, 4
    cfg, m, agg, jitted, abstract, shardings, batch_sh = _fleet_setup(
        mesh, method, n=n)
    data = _population_tokens(cfg, m, n, b, seq)
    mode = "rr_shared" if method == "diana_rr" else "rr"
    key = jax.random.key(4)

    with jax.set_mesh(mesh):
        # A: today's full-participation pipeline-fed loop
        state = jax.device_put(
            steps.init_train_state(jax.random.key(0), cfg, agg, m,
                                   mesh=mesh), shardings)
        sampler = ReshuffleSampler(m, n, mode=mode, seed=1)
        with make_batch_stream(
                data, sampler,
                put=lambda bt: jax.device_put(bt, batch_sh(bt))) as stream:
            for t in range(total):
                if method == "diana_rr":
                    slots = jnp.asarray(shared_slots_for_step(
                        sampler, t, n_slots=agg.n_slots))
                    state, _ = jitted(state, next(stream), key, slots)
                else:
                    state, _ = jitted(state, next(stream), key)
        ref = jax.device_get(state)

        # B: the fleet path with cohort == population
        state2 = jax.device_put(
            steps.init_train_state(jax.random.key(0), cfg, agg, m,
                                   mesh=mesh), shardings)
        store = ClientStateStore.create(
            abstract.params, m, WIRE_RULES[method], n_slots=agg.n_slots,
            dtype=np.float32, shard_size=3)
        with FleetRunner(jitted, abstract, shardings, batch_sh, agg=agg,
                         mesh=mesh, data=data,
                         sampler=ReshuffleSampler(m, n, mode=mode, seed=1),
                         cohorts=CohortSampler(m, m, seed=9),
                         store=store) as runner:
            state2 = runner.run(state2, key, total)
            bits_per_client = runner.checkpoint_meta()[
                "bits_per_client_round"]
        flt = jax.device_get(state2)

    for (pa, a), (_, bb) in zip(
            jax.tree_util.tree_leaves_with_path(ref.params),
            jax.tree_util.tree_leaves_with_path(flt.params)):
        assert np.asarray(a).tobytes() == np.asarray(bb).tobytes(), pa
    got = store.gather(np.arange(m))
    for (pa, a), (_, bb) in zip(
            jax.tree_util.tree_leaves_with_path(ref.shifts),
            jax.tree_util.tree_leaves_with_path(got)):
        assert np.asarray(a).tobytes() == np.asarray(bb).tobytes(), pa
    assert bits_per_client > 0
    assert (store.bits == total * bits_per_client).all()
    assert (store.cursor == total).all()


@needs_mesh
def test_fleet_resume_determinism(mesh_4x2, tmp_path):
    """Fleet --resume: checkpoint (TrainState + store + fleet cursor) cut
    mid-fleet-epoch at round 3 of a C=10/m=4 walk, restore into a fresh
    store, continue — metrics, params, store shifts, cursors, and bit
    counters all bit-match the uninterrupted run."""
    from repro.checkpoint import (
        load_meta, restore_fleet_checkpoint, save_fleet_checkpoint)
    from repro.core.rules import WIRE_RULES
    from repro.launch import steps

    mesh = mesh_4x2
    C, n, b, seq, total, cut = 10, 3, 1, 8, 6, 3
    cfg, m, agg, jitted, abstract, shardings, batch_sh = _fleet_setup(
        mesh, "diana", n=n)
    data = _population_tokens(cfg, C, n, b, seq)
    mk_store = lambda: ClientStateStore.create(
        abstract.params, C, WIRE_RULES["diana"], dtype=np.float32,
        shard_size=4)
    mk_runner = lambda start, store: FleetRunner(
        jitted, abstract, shardings, batch_sh, agg=agg, mesh=mesh,
        data=data, sampler=ReshuffleSampler(C, n, mode="rr", seed=1),
        cohorts=CohortSampler(C, m, seed=9), store=store, start_round=start)
    key = jax.random.key(4)
    path = str(tmp_path / "fleet.ckpt")

    with jax.set_mesh(mesh):
        state = jax.device_put(
            steps.init_train_state(jax.random.key(0), cfg, agg, m,
                                   mesh=mesh), shardings)
        store = mk_store()
        runner = mk_runner(0, store)
        losses_a = []

        def snap(t, st, metrics):
            losses_a.append(np.asarray(metrics["loss"]).tobytes())
            if t + 1 == cut:
                save_fleet_checkpoint(path, jax.device_get(st), store,
                                      step=t + 1,
                                      meta={"fleet":
                                            runner.checkpoint_meta()})

        with runner:
            state = runner.run(state, key, total, callback=snap)
        ref, ref_store = jax.device_get(state), store

        fm = load_meta(path)["meta"]["fleet"]
        assert fm["round"] == cut
        assert fm["epoch_position"] != 0, "cut must land mid-fleet-epoch"
        store_b = mk_store()
        state_b = restore_fleet_checkpoint(path, abstract, shardings,
                                           store_b)
        losses_b = []
        with mk_runner(fm["round"], store_b) as runner_b:
            state_b = runner_b.run(
                state_b, key, total - cut,
                callback=lambda t, st, mx: losses_b.append(
                    np.asarray(mx["loss"]).tobytes()))
        flt = jax.device_get(state_b)

    assert losses_b == losses_a[cut:]
    for (pa, a), (_, bb) in zip(
            jax.tree_util.tree_leaves_with_path(ref.params),
            jax.tree_util.tree_leaves_with_path(flt.params)):
        assert np.asarray(a).tobytes() == np.asarray(bb).tobytes(), pa
    everyone = np.arange(C)
    for (pa, a), (_, bb) in zip(
            jax.tree_util.tree_leaves_with_path(ref_store.gather(everyone)),
            jax.tree_util.tree_leaves_with_path(store_b.gather(everyone))):
        assert np.array_equal(a, bb), pa
    assert np.array_equal(ref_store.cursor, store_b.cursor)
    assert np.array_equal(ref_store.bits, store_b.bits)


@needs_mesh
def test_fleet_partial_participation_trains_and_isolates_state(mesh_4x2):
    """C=12 > m=4 on the production wire: the run trains (finite losses),
    only sampled clients' store rows move, device shift tables stay
    O(cohort), and a wrong-cursor store is rejected at resume."""
    from repro.launch import steps

    mesh = mesh_4x2
    C, n, b, seq, total = 12, 3, 1, 8, 2  # 2 of 3 cohorts per fleet epoch
    cfg, m, agg, jitted, abstract, shardings, batch_sh = _fleet_setup(
        mesh, "diana", n=n)
    data = _population_tokens(cfg, C, n, b, seq)
    from repro.core.rules import WIRE_RULES

    store = ClientStateStore.create(abstract.params, C,
                                    WIRE_RULES["diana"], dtype=np.float32,
                                    shard_size=5)
    cohorts = CohortSampler(C, m, seed=3)
    sampler = ReshuffleSampler(C, n, mode="rr", seed=1)
    with jax.set_mesh(mesh):
        state = jax.device_put(
            steps.init_train_state(jax.random.key(0), cfg, agg, m,
                                   mesh=mesh), shardings)
        losses = []
        with FleetRunner(jitted, abstract, shardings, batch_sh, agg=agg,
                         mesh=mesh, data=data, sampler=sampler,
                         cohorts=cohorts, store=store) as runner:
            state = runner.run(
                state, jax.random.key(2), total,
                callback=lambda t, st, mx: losses.append(
                    float(mx["loss"])))
    assert np.isfinite(losses).all()
    sampled = np.unique(np.concatenate(
        [cohorts.cohort_for_round(r) for r in range(total)]))
    unsampled = np.setdiff1d(np.arange(C), sampled)
    assert unsampled.size, "C=12/m=4/2 rounds must leave clients unsampled"
    for leaf in jax.tree.leaves(store.gather(unsampled)):
        assert np.abs(leaf).max() == 0
    touched = store.gather(sampled)
    assert any(np.abs(l).max() > 0 for l in jax.tree.leaves(touched))
    assert np.array_equal(store.cursor > 0, np.isin(np.arange(C), sampled))
    # device shift tables are cohort-sized, not population-sized
    for leaf in jax.tree.leaves(abstract.shifts):
        assert leaf.shape[0] == m
    # a store whose cursors disagree with the walk is rejected at resume,
    # and the error names the offending client ids (satellite: debuggable
    # cursor mismatches)
    store.advance(np.array([0]), 1)
    with pytest.raises(ValueError,
                       match=r"disagree with the cohort walk at round 2 "
                             r"for client ids \[0\]"):
        FleetRunner(jitted, abstract, shardings, batch_sh, agg=agg,
                    mesh=mesh, data=data, sampler=sampler, cohorts=cohorts,
                    store=store, start_round=total)


@needs_mesh
def test_fleet_slotted_gates(mesh_4x2):
    """diana_rr fleet configs that break the shared-slot contract are
    rejected up front: i.i.d. cohorts, a population not divisible by the
    cohort (straddling cohorts mix data positions), and non-shared
    sampler orders (DESIGN.md §3.9)."""
    from repro.core.rules import WIRE_RULES

    mesh = mesh_4x2
    n = 3
    cfg, m, agg, jitted, abstract, shardings, batch_sh = _fleet_setup(
        mesh, "diana_rr", n=n)
    mk = lambda C, cmode, smode, ls=1: FleetRunner(
        jitted, abstract, shardings, batch_sh, agg=agg, mesh=mesh,
        data=_population_tokens(cfg, C, n, 1, 8),
        sampler=ReshuffleSampler(C, n, mode=smode, seed=1),
        cohorts=CohortSampler(C, m, mode=cmode, seed=2),
        store=ClientStateStore.create(abstract.params, C,
                                      WIRE_RULES["diana_rr"], n_slots=n,
                                      dtype=np.float32), local_steps=ls)
    with jax.set_mesh(mesh):
        with pytest.raises(ValueError, match="shared-slot"):
            mk(8, "with_replacement", "rr_shared")
        with pytest.raises(ValueError, match="divisible"):
            mk(10, "rr", "rr_shared")
        with pytest.raises(ValueError, match="rr_shared"):
            mk(8, "rr", "rr")
        # flat-mesh NASTYA collapses the outer slot tables to one row
        # (the inter-pod wire carries the slot-free epoch gradient), so a
        # 3-slot store no longer matches the wire's table layout
        with pytest.raises(ValueError, match="store n_slots=3"):
            mk(8, "rr", "rr_shared", ls=2)
        runner = mk(8, "rr", "rr_shared")  # valid: 8 % 4 == 0
        runner.close()


# ---------------------------------------------------------------------------
# chaos: deterministic fault injection + buffered-async round planning
# ---------------------------------------------------------------------------

def test_async_planner_clean_run_is_exactly_synchronous():
    """No chaos, buffer_k == m: everyone on time, weight EXACTLY 1.0 per
    rank (the elastic step's bitwise no-op), everyone completes/reports —
    and the plan is a pure function of (seed, round)."""
    p = AsyncPlanner(6)
    cohort = np.arange(6)
    for rnd in range(4):
        plan = p(rnd, cohort)
        assert (plan.weights == np.float32(1.0)).all()
        assert plan.completes.all() and plan.reported.all()
        assert np.isfinite(plan.deadline)
    q = AsyncPlanner(6)
    for rnd in range(4):
        a, b = p(rnd, cohort), q(rnd, cohort)
        assert np.array_equal(a.weights, b.weights)
        assert np.array_equal(a.completes, b.completes)
        assert np.array_equal(a.latency, b.latency)


def test_async_planner_k_of_m_late_policies():
    """buffer_k=2 of m=4 with stragglers: under 'drop' the late reports
    get weight 0 and never complete (but still burn uplink bits — reported
    stays True); under 'discount' everyone alive completes with a
    staleness-damped weight; both normalize so sum(weights) == m."""
    m = 4
    chaos = ChaosConfig(straggler=0.5, delay=2.0, seed=7)
    cohort = np.arange(m)
    drop = AsyncPlanner(m, buffer_k=2, late="drop", chaos=chaos)
    disc = AsyncPlanner(m, buffer_k=2, late="discount", discount=0.5,
                        chaos=chaos)
    saw_dropped_late = False
    for rnd in range(12):
        pd, pc = drop(rnd, cohort), disc(rnd, cohort)
        # same latency stream (same chaos seed), different fold-in policy
        assert np.array_equal(pd.latency, pc.latency)
        assert pd.deadline == pc.deadline
        assert np.array_equal(pd.completes, pd.weights > 0)
        assert pd.reported.all(), "no dropout: everyone transmits"
        assert pc.completes.all(), "discount folds every alive report in"
        saw_dropped_late |= bool((pd.reported & ~pd.completes).any())
        np.testing.assert_allclose(pd.weights.sum(), m, rtol=1e-6)
        np.testing.assert_allclose(pc.weights.sum(), m, rtol=1e-6)
        on_time = pc.latency <= pc.deadline
        assert (pc.weights[on_time] >= pc.weights.max() - 1e-6).all()
        late = pc.completes & ~on_time
        if late.any():
            assert (pc.weights[late] < pc.weights[on_time].min()).all(), \
                "stale reports fold in at a strictly smaller weight"
    assert saw_dropped_late, "12 rounds at straggler=0.5 must drop someone"


def test_async_planner_elastic_resize_pads_with_zero_weight():
    """resize(r)=2 on an m=4 step: ranks past the active count are padding
    — weight 0, never reported (no bits), never complete (no cursor
    advance), latency inf — so the compiled shape never changes."""
    p = AsyncPlanner(4, chaos=ChaosConfig(seed=1),
                     resize=lambda r: 2 if r % 2 == 0 else 4)
    plan = p(0, np.arange(4))
    assert (plan.weights[2:] == 0).all()
    assert not plan.reported[2:].any() and not plan.completes[2:].any()
    assert np.isinf(plan.latency[2:]).all()
    assert plan.completes[:2].all()
    np.testing.assert_allclose(plan.weights.sum(), 4, rtol=1e-6)
    grown = p(1, np.arange(4))
    assert grown.completes.all(), "odd rounds run the full cohort again"
    with pytest.raises(ValueError, match="outside"):
        AsyncPlanner(4, resize=lambda r: 0)(0, np.arange(4))


def test_async_planner_zero_alive_round():
    """dropout can darken the whole cohort: the plan reports an empty
    round (deadline inf, no weights) instead of dividing by zero — the
    driver skips the jitted launch entirely."""
    p = AsyncPlanner(4, chaos=ChaosConfig(dropout=0.9, seed=0))
    cohort = np.arange(4)
    rnd = next(r for r in range(64) if not p(r, cohort).reported.any())
    plan = p(rnd, cohort)
    assert plan.deadline == np.inf
    assert (plan.weights == 0).all() and not plan.completes.any()


def test_async_planner_may_defer_matrix_and_validation():
    """`may_defer` is the slotted-methods gate: anything that can finish a
    round without advancing a client's cursor trips it."""
    assert not AsyncPlanner(4).may_defer
    assert not AsyncPlanner(
        4, buffer_k=2, chaos=ChaosConfig(straggler=0.5)).may_defer
    assert AsyncPlanner(4, late="drop").may_defer
    assert AsyncPlanner(4, chaos=ChaosConfig(dropout=0.1)).may_defer
    assert AsyncPlanner(4, resize=lambda r: 4).may_defer
    with pytest.raises(ValueError, match="buffer_k"):
        AsyncPlanner(4, buffer_k=0)
    with pytest.raises(ValueError, match="buffer_k"):
        AsyncPlanner(4, buffer_k=5)
    with pytest.raises(ValueError, match="late"):
        AsyncPlanner(4, late="bogus")
    with pytest.raises(ValueError, match="discount"):
        AsyncPlanner(4, discount=0.0)
    with pytest.raises(ValueError, match="dropout"):
        ChaosConfig(dropout=1.0)
    with pytest.raises(ValueError, match="delay"):
        ChaosConfig(delay=-0.5)


def test_async_planner_on_time_metric_regression():
    """`on_time` must come from the plan (`alive & (latency <= deadline)`),
    NOT from thresholding the normalized weights: the m/sum(w) rescale
    exceeds 1.0 whenever any client is late or dark, so at late='discount'
    with discount=1.0 a small-staleness late report's weight crosses 1.0
    and the weight-threshold count claims a LATE client was on time."""
    m = 4
    planner = AsyncPlanner(
        m, buffer_k=2, late="discount", discount=1.0,
        chaos=ChaosConfig(dropout=0.3, straggler=0.5, delay=0.2, seed=7))
    cohort = np.arange(m)
    miscounted = []
    for r in range(100):
        plan = planner(r, cohort)
        # the plan's on_time is definitionally alive-and-within-deadline
        assert np.array_equal(plan.on_time,
                              ~np.isinf(plan.latency)
                              & (plan.latency <= plan.deadline))
        if int((plan.weights >= 1.0).sum()) != int(plan.on_time.sum()):
            miscounted.append(r)
            # every miscount is a LATE/dark-rescaled weight >= 1, never a
            # missing on-time client
            assert ((plan.weights >= 1.0) & ~plan.on_time).any()
    assert 4 in miscounted, "seed 7 round 4 is the pinned repro"
    assert len(miscounted) > 10, "the miscount is systematic, not a fluke"
    # clean synchronous round: all weights exactly 1.0 AND all on time —
    # the two counts agree, which is why the bug stayed invisible
    clean = AsyncPlanner(m)(0, cohort)
    assert clean.on_time.all() and (clean.weights == 1.0).all()
    # zero-alive rounds report nobody on time
    dead = AsyncPlanner(
        m, chaos=ChaosConfig(dropout=0.99, seed=1))
    for r in range(200):
        plan = dead(r, cohort)
        if not (~np.isinf(plan.latency)).any():
            assert not plan.on_time.any()
            break
    else:
        pytest.fail("dropout=0.99 over 200 rounds must kill one round")


def test_faulty_store_injects_cursor_and_bit_writes():
    """Chaos store-fail coverage includes `advance`/`add_bits` (the cursor
    and bit writes), not just gather/scatter: they draw from the SAME
    (seed, call-index) stream, injection happens BEFORE the op (a failed
    advance leaves cursors untouched), and `touch`/`as_tree` still
    delegate uninjected (prefetch warming and checkpoint reads must not
    perturb the I/O schedule)."""
    from repro.core.rules import get_rule

    store = ClientStateStore.create(_params(), 6, get_rule("single"),
                                    shard_size=3)
    chaos = ChaosConfig(store_fail=0.5, seed=3)
    cohort = np.array([0, 1])

    def pattern(fs, op, ops=30):
        out = []
        for _ in range(ops):
            try:
                op(fs)
                out.append(False)
            except TransientStoreError:
                out.append(True)
        return out

    pat_adv = pattern(FaultyStore(store, chaos), lambda fs: fs.advance(cohort, 1))
    assert any(pat_adv) and not all(pat_adv)
    # same call-index stream: add_bits at the same indices fails identically
    assert pattern(FaultyStore(store, chaos),
                   lambda fs: fs.add_bits(cohort, 8.0)) == pat_adv
    # inject-before-op atomicity: a failing advance never moved the cursor
    store.cursor[...] = 0
    store.bits[...] = 0.0
    fs = FaultyStore(store, chaos)
    applied = 0
    for _ in range(30):
        try:
            fs.advance(cohort, 1)
            applied += 1
        except TransientStoreError:
            assert store.cursor[cohort].min() == applied, \
                "a failed advance must not move the cursor"
    assert (store.cursor[cohort] == applied).all()
    # the fresh wrapper replays the same schedule: failures line up
    assert 30 - applied == sum(pat_adv)
    # uninjected delegation: warming + checkpoint reads never fault and
    # never consume a call index
    before = fs.injected_failures
    for _ in range(50):
        fs.touch(cohort)
        fs.as_tree()
    assert fs.injected_failures == before


def test_faulty_store_deterministic_and_atomic():
    """Injected store failures are a pure function of (seed, call index):
    a replay reproduces the exact failure schedule. Injection happens
    BEFORE the underlying op, so a failed scatter leaves the store
    untouched and the retry cannot double-apply."""
    from repro.core.rules import get_rule

    store = ClientStateStore.create(_params(), 6, get_rule("single"),
                                    shard_size=3)
    chaos = ChaosConfig(store_fail=0.5, seed=3)
    cohort = np.array([0, 1])

    def pattern(fs, ops=30):
        out = []
        for _ in range(ops):
            try:
                fs.gather(cohort)
                out.append(False)
            except TransientStoreError:
                out.append(True)
        return out

    fs = FaultyStore(store, chaos)
    pat = pattern(fs)
    assert any(pat) and not all(pat), "store_fail=0.5 over 30 calls"
    assert fs.injected_failures == sum(pat)
    assert pattern(FaultyStore(store, chaos)) == pat, "same seed, same faults"
    # atomicity: keep fs's call index rolling past the gather probes
    before = store.gather(cohort)
    upd = jax.tree.map(lambda x: x + 1.0, before)
    applied = False
    for _ in range(10):
        try:
            fs.scatter(cohort, upd)
            applied = True
            break
        except TransientStoreError:
            for k in before:
                assert np.array_equal(store.gather(cohort)[k], before[k]), \
                    "a failed scatter must not touch the store"
    assert applied, "bounded retries must eventually land at fail=0.5"
    for k in upd:
        assert np.array_equal(store.gather(cohort)[k], upd[k])
    # everything but gather/scatter delegates to the wrapped store
    assert fs.population == 6
    assert np.array_equal(fs.cursor, store.cursor)


def test_async_stream_exactly_once_rr_under_dropout():
    """THE exactly-once acceptance criterion, host-side: with seeded
    dropout + stragglers and late='drop', a client's cursor advances ONLY
    when its report completes — so a dropped client re-reads the SAME RR
    position next time it is sampled, every consumed position is the
    contiguous walk of its own epoch permutations, and every completed
    data epoch is a full permutation (>= 3 epochs per client). A stream
    rebuilt at `start_round` replays the planner over the skipped prefix
    and lands on identical cursors/batches."""
    C, n, b, m, total, restart = 8, 3, 1, 4, 48, 31
    rng = np.random.default_rng(0)
    data = {"x": rng.normal(size=(C, n, b, 2)).astype(np.float32)}
    sampler = ReshuffleSampler(C, n, mode="rr", seed=1)
    cohorts = CohortSampler(C, m, seed=2)
    planner = AsyncPlanner(m, buffer_k=3, late="drop",
                           chaos=ChaosConfig(dropout=0.25, straggler=0.3,
                                             delay=1.0, seed=13))
    counts = np.zeros(C, np.int64)
    consumed = [[] for _ in range(C)]
    deferrals = 0
    tail = []
    with CohortStream(data, sampler, cohorts, prefetch=False,
                      planner=planner) as stream:
        for t in range(total):
            fr = next(stream)
            assert fr.plan is not None
            for i, c in enumerate(fr.cohort):
                e, pos = divmod(counts[c], n)
                want = sampler.epoch_order(e)[c, pos]
                # sampled clients always read from their OWN cursor —
                # including clients about to be dropped, who will re-read
                # this very position next time
                assert fr.cols[i, 0] == want, (t, c)
                assert np.array_equal(fr.batch["x"][i * b:(i + 1) * b],
                                      data["x"][c, want])
                if fr.plan.completes[i]:
                    consumed[c].append(int(want))
            deferrals += int((~fr.plan.completes).sum())
            counts[fr.cohort[fr.plan.completes]] += 1
            if t >= restart:
                tail.append((fr.cohort.copy(), fr.batch["x"].copy()))
    assert deferrals > 0, "chaos at these rates must defer someone"
    assert counts.min() >= 3 * n, \
        f"every client needs >= 3 completed epochs, got {counts}"
    for c in range(C):
        assert len(consumed[c]) == counts[c]
        for e in range(counts[c] // n):  # every COMPLETED epoch
            assert sorted(consumed[c][e * n:(e + 1) * n]) == list(range(n)), \
                (c, e, consumed[c])
    # resume: replaying the planner over [0, restart) lands mid-chaos
    with CohortStream(data, sampler, cohorts, prefetch=False,
                      planner=planner, start_round=restart) as resumed:
        for cohort, x in tail:
            fr = next(resumed)
            assert np.array_equal(fr.cohort, cohort)
            assert np.array_equal(fr.batch["x"], x)


# ---------------------------------------------------------------------------
# production acceptance: buffered-async fleet on the compiled elastic step
# ---------------------------------------------------------------------------

@needs_mesh
@pytest.mark.parametrize("method", ["diana", "diana_rr"])
def test_async_clean_run_bit_matches_sync_fleet(method, mesh_4x2):
    """Chaos off + buffer_k == cohort size: the AsyncFleetRunner on the
    ELASTIC compiled step (weights vector all-1.0) walks a bitwise
    identical trajectory to the synchronous FleetRunner on the non-elastic
    step — params, store shift tables, bits, cursors — for both the
    single-shift and the per-slot wire."""
    from repro.core.rules import WIRE_RULES
    from repro.launch import steps

    mesh = mesh_4x2
    n, b, seq, total = 3, 1, 8, 4
    mode = "rr_shared" if method == "diana_rr" else "rr"
    key = jax.random.key(4)

    def run(async_mode):
        cfg, m, agg, jitted, abstract, shardings, batch_sh = _fleet_setup(
            mesh, method, n=n, elastic=async_mode)
        data = _population_tokens(cfg, m, n, b, seq)
        store = ClientStateStore.create(
            abstract.params, m, WIRE_RULES[method], n_slots=agg.n_slots,
            dtype=np.float32, shard_size=3)
        cls = AsyncFleetRunner if async_mode else FleetRunner
        with jax.set_mesh(mesh):
            state = jax.device_put(
                steps.init_train_state(jax.random.key(0), cfg, agg, m,
                                       mesh=mesh), shardings)
            with cls(jitted, abstract, shardings, batch_sh, agg=agg,
                     mesh=mesh, data=data,
                     sampler=ReshuffleSampler(m, n, mode=mode, seed=1),
                     cohorts=CohortSampler(m, m, seed=9),
                     store=store) as runner:
                state = runner.run(state, key, total)
        return jax.device_get(state), store

    ref, ref_store = run(False)
    got, got_store = run(True)
    for (pa, a), (_, bb) in zip(
            jax.tree_util.tree_leaves_with_path(ref.params),
            jax.tree_util.tree_leaves_with_path(got.params)):
        assert np.asarray(a).tobytes() == np.asarray(bb).tobytes(), pa
    everyone = np.arange(ref_store.population)
    for (pa, a), (_, bb) in zip(
            jax.tree_util.tree_leaves_with_path(ref_store.gather(everyone)),
            jax.tree_util.tree_leaves_with_path(got_store.gather(everyone))):
        assert np.asarray(a).tobytes() == np.asarray(bb).tobytes(), pa
    assert np.array_equal(ref_store.bits, got_store.bits)
    assert np.array_equal(ref_store.cursor, got_store.cursor)


@needs_mesh
def test_async_fleet_resume_under_chaos_bit_exact(mesh_4x2, tmp_path):
    """Mid-walk fleet checkpoint UNDER chaos (dropout + stragglers +
    injected store failures with bounded retry) resumes bit-exactly: the
    rebuilt stream replays the planner over the skipped rounds, the
    FaultyStore wrapper re-arms, and metrics/params/store all match the
    uninterrupted run."""
    from repro.checkpoint import (
        load_meta, restore_fleet_checkpoint, save_fleet_checkpoint)
    from repro.core.rules import WIRE_RULES
    from repro.launch import steps

    mesh = mesh_4x2
    C, n, b, seq, total, cut = 8, 3, 1, 8, 6, 3
    cfg, m, agg, jitted, abstract, shardings, batch_sh = _fleet_setup(
        mesh, "diana", n=n, elastic=True)
    data = _population_tokens(cfg, C, n, b, seq)
    chaos = ChaosConfig(dropout=0.2, straggler=0.4, delay=1.0,
                        store_fail=0.3, max_retries=3, seed=5)
    mk_store = lambda: ClientStateStore.create(
        abstract.params, C, WIRE_RULES["diana"], dtype=np.float32,
        shard_size=3)
    mk_runner = lambda start, store: AsyncFleetRunner(
        jitted, abstract, shardings, batch_sh, agg=agg, mesh=mesh,
        data=data, sampler=ReshuffleSampler(C, n, mode="rr", seed=1),
        cohorts=CohortSampler(C, m, seed=9), store=store, buffer_k=3,
        late="drop", chaos=chaos, start_round=start)
    key = jax.random.key(4)
    path = str(tmp_path / "fleet_async.ckpt")
    trace = lambda mx: (b"skip" if mx.get("skipped")
                        else np.asarray(mx["loss"]).tobytes())

    with jax.set_mesh(mesh):
        state = jax.device_put(
            steps.init_train_state(jax.random.key(0), cfg, agg, m,
                                   mesh=mesh), shardings)
        store = mk_store()
        runner = mk_runner(0, store)
        losses_a = []
        on_time_a = {}

        def snap(t, st, metrics):
            losses_a.append(trace(metrics))
            if "on_time" in metrics:
                on_time_a[t] = metrics["on_time"]
            if t + 1 == cut:
                save_fleet_checkpoint(path, jax.device_get(st), store,
                                      step=t + 1,
                                      meta={"fleet":
                                            runner.checkpoint_meta()})

        with runner:
            state = runner.run(state, key, total, callback=snap)
        ref, ref_store = jax.device_get(state), store

        fm = load_meta(path)["meta"]["fleet"]
        assert fm["round"] == cut
        assert fm["async"]["chaos"]["dropout"] == 0.2
        store_b = mk_store()
        state_b = restore_fleet_checkpoint(path, abstract, shardings,
                                           store_b)
        losses_b = []
        with mk_runner(fm["round"], store_b) as runner_b:
            state_b = runner_b.run(
                state_b, key, total - cut,
                callback=lambda t, st, mx: losses_b.append(trace(mx)))
        flt = jax.device_get(state_b)

    assert losses_b == losses_a[cut:]
    for (pa, a), (_, bb) in zip(
            jax.tree_util.tree_leaves_with_path(ref.params),
            jax.tree_util.tree_leaves_with_path(flt.params)):
        assert np.asarray(a).tobytes() == np.asarray(bb).tobytes(), pa
    everyone = np.arange(C)
    for (pa, a), (_, bb) in zip(
            jax.tree_util.tree_leaves_with_path(ref_store.gather(everyone)),
            jax.tree_util.tree_leaves_with_path(store_b.gather(everyone))):
        assert np.array_equal(a, bb), pa
    assert np.array_equal(ref_store.cursor, store_b.cursor)
    assert np.array_equal(ref_store.bits, store_b.bits)
    # under drop + dropout some clients must sit below the full walk
    assert ref_store.cursor.sum() < \
        CohortSampler(C, m, seed=9).participation_counts(total).sum()
    # with advance/add_bits inside the injected+retried I/O set, the
    # chaos run's cursors must STILL equal the closed-form planner replay
    # of the walk — an injected-but-unretried cursor write would drift
    cohorts = CohortSampler(C, m, seed=9)
    planner = AsyncPlanner(m, buffer_k=3, late="drop", chaos=chaos)
    replay = np.zeros(C, np.int64)
    for t in range(total):
        cohort = cohorts.cohort_for_round(t)
        plan = planner(t, cohort)
        replay[cohort[plan.completes]] += 1
        if t in on_time_a:
            # driver metric == plan truth (the weight-threshold count
            # overstated it whenever a late weight rescaled past 1.0)
            assert on_time_a[t] == int(plan.on_time.sum()), t
    assert np.array_equal(ref_store.cursor, replay)


@needs_mesh
def test_fleet_mean_scale_tracks_population_mean(mesh_4x2):
    """PR-5 carry-over (a): with `mean_scale = M/C` the device-resident
    mean shift integrates beta = (M/C) * alpha per round, which is exactly
    the population mean of the per-client store shifts — not the
    (C/M)-inflated cohort estimate the unscaled update would keep."""
    from repro.core.rules import WIRE_RULES
    from repro.launch import steps

    mesh = mesh_4x2
    C, n, b, seq, total = 8, 3, 1, 8, 4  # 2 whole fleet epochs
    cfg, m, agg, jitted, abstract, shardings, batch_sh = _fleet_setup(
        mesh, "diana", n=n, mean_scale=0.5)  # m/C = 4/8
    data = _population_tokens(cfg, C, n, b, seq)
    store = ClientStateStore.create(abstract.params, C, WIRE_RULES["diana"],
                                    dtype=np.float32, shard_size=3)
    with jax.set_mesh(mesh):
        state = jax.device_put(
            steps.init_train_state(jax.random.key(0), cfg, agg, m,
                                   mesh=mesh), shardings)
        with FleetRunner(jitted, abstract, shardings, batch_sh, agg=agg,
                         mesh=mesh, data=data,
                         sampler=ReshuffleSampler(C, n, mode="rr", seed=1),
                         cohorts=CohortSampler(C, m, seed=3),
                         store=store) as runner:
            state = runner.run(state, jax.random.key(2), total)
    mean_shift = jax.device_get(state.mean_shift)
    got = store.gather(np.arange(C))
    moved = False
    for (pa, h_bar), (_, rows) in zip(
            jax.tree_util.tree_leaves_with_path(mean_shift),
            jax.tree_util.tree_leaves_with_path(got)):
        pop_mean = np.asarray(rows, np.float64).mean(axis=0)
        np.testing.assert_allclose(np.asarray(h_bar), pop_mean.astype(
            np.float32), atol=1e-5, err_msg=str(pa))
        moved |= bool(np.abs(np.asarray(h_bar)).max() > 0)
    assert moved, "4 rounds of DIANA must move the mean shift"


@needs_mesh
def test_fleet_flat_nastya_pod_shift_roundtrip(mesh_4x2):
    """PR-5 carry-over (b): flat-mesh NASTYA (local_steps > 1 maps every
    client onto its own pod) now RUNS as a fleet — the driver round-trips
    `TrainState.pod_shifts` through the store instead of rejecting the
    config. Sampled clients' rows move, cursors advance by local_steps per
    participation, and device tables stay O(cohort)."""
    from repro.core.rules import WIRE_RULES
    from repro.launch import steps

    mesh = mesh_4x2
    C, n, b, seq, total, ls = 12, 4, 1, 8, 2, 2
    cfg, m, agg, jitted, abstract, shardings, batch_sh = _fleet_setup(
        mesh, "diana", n=n, local_steps=ls)
    assert abstract.shifts is None and abstract.pod_shifts is not None, \
        "flat NASTYA keeps per-client DIANA state in the pod tables"
    data = _population_tokens(cfg, C, n, b, seq)
    store = ClientStateStore.create(abstract.params, C, WIRE_RULES["diana"],
                                    dtype=np.float32, shard_size=3)
    cohorts = CohortSampler(C, m, seed=3)
    with jax.set_mesh(mesh):
        state = jax.device_put(
            steps.init_train_state(jax.random.key(0), cfg, agg, m,
                                   mesh=mesh, local_steps=ls), shardings)
        losses = []
        with FleetRunner(jitted, abstract, shardings, batch_sh, agg=agg,
                         mesh=mesh, data=data,
                         sampler=ReshuffleSampler(C, n, mode="rr", seed=1),
                         cohorts=cohorts, store=store,
                         local_steps=ls) as runner:
            state = runner.run(
                state, jax.random.key(2), total,
                callback=lambda t, st, mx: losses.append(
                    float(mx["loss"])))
    assert np.isfinite(losses).all() and len(losses) == total
    sampled = np.unique(np.concatenate(
        [cohorts.cohort_for_round(r) for r in range(total)]))
    unsampled = np.setdiff1d(np.arange(C), sampled)
    assert unsampled.size, "2 rounds of C=12/m=4 leave clients unsampled"
    touched = store.gather(sampled)
    assert any(np.abs(l).max() > 0 for l in jax.tree.leaves(touched)), \
        "pod_shifts must round-trip into the store"
    for leaf in jax.tree.leaves(store.gather(unsampled)):
        assert np.abs(leaf).max() == 0
    assert np.array_equal(store.cursor,
                          cohorts.participation_counts(total) * ls)
    for leaf in jax.tree.leaves(jax.device_get(state.pod_shifts)):
        assert leaf.shape[0] == m, "device tables stay cohort-sized"
