"""data/paging — out-of-core fleet data (DESIGN.md §3.11).

Covers the on-disk `ClientDataStore` layout (sharded per-client rows, lazy
shard files, spec round-trip), the `LookaheadPager`'s windowed eviction and
LRU bounds, and THE acceptance criterion: a `CohortStream(paged=...)` —
and the fleet drivers on top of it — emits bit-identical batches and walks
a bit-identical trajectory (params, shift tables, bits, cursors) vs the
in-RAM client-stacked path, for `diana` AND `diana_rr`, including
`--resume` mid-walk and under seeded `AsyncPlanner` dropout (exactly-once
RR: non-completers must NOT advance page cursors).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.paging import ClientDataStore, LookaheadPager
from repro.data.pipeline import CohortStream
from repro.data.reshuffle import ReshuffleSampler
from repro.fleet import (AsyncFleetRunner, AsyncPlanner, ChaosConfig,
                         CohortSampler, ClientStateStore, FleetRunner)

needs_mesh = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs 8 forced host devices"
)


def _stacked(C, n=3, b=1, seq=4, seed=0):
    """Two-leaf client-stacked population tree (the in-RAM reference)."""
    rng = np.random.default_rng(seed)
    return {
        "tokens": rng.integers(0, 97, (C, n, b, seq), dtype=np.int32),
        "mask": rng.random((C, n, b, seq), dtype=np.float32),
    }


# ---------------------------------------------------------------------------
# ClientDataStore: the on-disk layout
# ---------------------------------------------------------------------------

def test_data_store_roundtrip_and_spec(tmp_path):
    """from_stacked -> pages/open('r') reproduce the source tree exactly
    (shard boundaries and the short last shard included), the JSON spec
    round-trips, and the sizing helpers agree with the stacked bytes."""
    C, shard = 10, 3
    data = _stacked(C)
    path = str(tmp_path / "store")
    ds = ClientDataStore.from_stacked(path, data, shard_size=shard)

    assert ds.population == C and ds.shard_size == shard
    assert ds.num_shards == 4 and ds.shard_rows(3) == 1  # 3+3+3+1
    assert sorted(ds.leaf_names) == ["mask", "tokens"]
    assert ds.n_batches == 3
    for name, arr in data.items():
        for s in range(ds.num_shards):
            lo = s * shard
            page = ds.page(name, s)
            assert page.dtype == arr.dtype
            assert np.array_equal(page, arr[lo:lo + ds.shard_rows(s)]), \
                (name, s)
        assert ds.page_nbytes(name) == shard * arr[0].nbytes

    ro = ClientDataStore.open(path)
    assert ro.spec() == ds.spec()
    assert np.array_equal(ro.page("tokens", 1), data["tokens"][3:6])
    assert ds.nbytes == sum(a.nbytes for a in data.values())
    assert ds.nbytes == ClientDataStore.estimate_nbytes(
        {name: arr[0] for name, arr in data.items()}, C)


def test_data_store_lazy_shards(tmp_path):
    """`create` writes only the spec; absent shards read as zeros; a
    partial `write_rows` creates exactly the touched shard files — the
    1e6-client dry-run path must not pay disk for untouched clients."""
    path = str(tmp_path / "sparse")
    ds = ClientDataStore.create(
        path, 100, {"x": jax.ShapeDtypeStruct((2, 1, 4), jnp.float32)},
        shard_size=8)
    assert os.listdir(path) == ["data_store.json"]
    assert np.array_equal(ds.page("x", 5), np.zeros((8, 2, 1, 4), np.float32))

    rows = np.arange(2 * 2 * 1 * 4, dtype=np.float32).reshape(2, 2, 1, 4)
    ds.write_rows(np.array([3, 17]), {"x": rows})  # shards 0 and 2 only
    dats = sorted(f for f in os.listdir(path) if f.endswith(".dat"))
    assert dats == ["x.0.dat", "x.2.dat"]
    assert np.array_equal(ds.page("x", 0)[3], rows[0])
    assert np.array_equal(ds.page("x", 2)[1], rows[1])
    assert np.array_equal(ds.page("x", 0)[0], np.zeros((2, 1, 4)))
    assert np.array_equal(ds.page("x", 1), np.zeros((8, 2, 1, 4)))
    # reopen writable and overwrite one client's rows in place
    rw = ClientDataStore.open(path, mode="r+")
    rw.write_rows(np.array([3]), {"x": rows[1:] + 1})
    assert np.array_equal(ds.page("x", 0)[3], rows[1] + 1)


def test_data_store_validation(tmp_path):
    data = _stacked(4)
    with pytest.raises(ValueError, match="population"):
        ClientDataStore.create(str(tmp_path / "a"), 0, {"x": data["tokens"][0]})
    with pytest.raises(ValueError, match="non-empty"):
        ClientDataStore.create(str(tmp_path / "b"), 4, {})
    with pytest.raises(ValueError, match=r"\(n, b, \.\.\.\)"):
        ClientDataStore.create(
            str(tmp_path / "c"), 4,
            {"x": jax.ShapeDtypeStruct((3,), jnp.float32)})
    with pytest.raises(ValueError, match="client-stacked"):
        ClientDataStore.from_stacked(
            str(tmp_path / "d"), {"x": np.zeros((4, 3))})
    with pytest.raises(ValueError, match="holds 5 clients"):
        ClientDataStore.from_stacked(
            str(tmp_path / "e"),
            {"x": np.zeros((4, 3, 1)), "y": np.zeros((5, 3, 1))})
    with pytest.raises(ValueError, match="mode"):
        ClientDataStore.open(str(tmp_path / "f"), mode="w")
    with pytest.raises(OSError, match="not a client data store"):
        ClientDataStore.open(str(tmp_path / "nope"))
    # unwritable location: a FILE where the directory should go
    blocker = tmp_path / "blocker"
    blocker.write_text("x")
    with pytest.raises(OSError, match="not a writable directory"):
        ClientDataStore.create(str(blocker / "sub"), 4,
                               {"x": data["tokens"][0]})

    ds = ClientDataStore.from_stacked(str(tmp_path / "g"), data, shard_size=2)
    with pytest.raises(ValueError, match=r"outside \[0, 4\)"):
        ds.write_rows(np.array([4]), {"tokens": data["tokens"][:1]})
    with pytest.raises(ValueError, match="rows shape"):
        ds.write_rows(np.array([0]), {"tokens": data["tokens"]})
    ro = ClientDataStore.open(str(tmp_path / "g"))
    with pytest.raises(OSError, match="read-only"):
        ro.write_rows(np.array([0]), {"tokens": data["tokens"][:1]})


# ---------------------------------------------------------------------------
# LookaheadPager: windowed residency + LRU
# ---------------------------------------------------------------------------

def test_pager_window_eviction_and_sizing(tmp_path):
    """A windowed cohort walk keeps residency under
    `resident_bound_nbytes(m)` at every round regardless of population,
    and the lookahead turns the next round's reads into cache hits."""
    C, m, shard = 48, 4, 3
    data = _stacked(C)
    ds = ClientDataStore.from_stacked(str(tmp_path / "s"), data,
                                      shard_size=shard)
    pager = LookaheadPager(ds, lookahead=1)
    cs = CohortSampler(C, m, seed=7)
    bound = pager.resident_bound_nbytes(m)
    assert bound < ds.nbytes, "bound must beat holding the population"
    for t in range(24):  # 2 fleet epochs
        for c in cs.cohort_for_round(t):
            for name in ds.leaf_names:
                np.testing.assert_array_equal(pager.views[name][c],
                                              data[name][c])
        pager.advance_window(t, cs)
        assert pager.resident_nbytes() <= bound, t
        assert pager.resident_pages() <= 2 * m * len(ds.leaf_names), t
    st = pager.stats()
    assert st["evictions"] > 0, "window must drop out-of-window pages"
    assert st["hits"] > st["misses"], "prefetch must convert reads to hits"


def test_pager_cold_random_access_lru(tmp_path):
    """Outside the windowed walk (a resumed run's first lookups, debug
    pokes) the optional `max_resident` cap LRU-bounds the cache while
    reads stay correct."""
    C = 30
    data = _stacked(C, n=2)
    ds = ClientDataStore.from_stacked(str(tmp_path / "s"), data, shard_size=2)
    pager = LookaheadPager(ds, lookahead=0, max_resident=3)
    order = np.random.default_rng(3).permutation(C)
    for c in order:
        np.testing.assert_array_equal(pager.views["tokens"][c],
                                      data["tokens"][c])
        np.testing.assert_array_equal(pager.views["mask"][c],
                                      data["mask"][c])
        assert pager.resident_pages() <= 3
    assert pager.evictions > 0
    # re-reads after eviction still correct (pages reload from disk)
    np.testing.assert_array_equal(pager.views["tokens"][int(order[0])],
                                  data["tokens"][int(order[0])])


def test_pager_store_binding_and_warming(tmp_path):
    """gather/scatter route through the bound `ClientStateStore` (the
    drivers bind AFTER any chaos wrap so `_io_retry` covers paged reads),
    and `advance_window` pre-touches the next cohort's shift rows."""
    from repro.core.rules import get_rule

    C = 8
    ds = ClientDataStore.from_stacked(str(tmp_path / "s"), _stacked(C),
                                      shard_size=3)
    pager = LookaheadPager(ds, lookahead=1)
    with pytest.raises(RuntimeError, match="bind_store"):
        pager.gather(np.array([0]))
    with pytest.raises(RuntimeError, match="bind_store"):
        pager.scatter(np.array([0]), {})

    params = {"w": jnp.zeros((2, 3), jnp.float32)}
    store = ClientStateStore.create(params, C, get_rule("single"),
                                    shard_size=3)
    pager.bind_store(store)
    cohort = np.array([1, 5])
    got = pager.gather(cohort)
    got = jax.tree_util.tree_map(lambda a: np.asarray(a) + 2.0, got)
    pager.scatter(cohort, got)
    direct = store.gather(cohort)
    assert np.array_equal(np.asarray(direct["w"]),
                          np.full((2, 2, 3), 2.0, np.float32))
    assert pager.state_bytes_warmed == 0
    pager.advance_window(0, CohortSampler(C, 2, seed=1))
    assert pager.state_bytes_warmed > 0, "next cohort's shifts pre-touched"


# ---------------------------------------------------------------------------
# CohortStream(paged=...): THE bit-equality contract (host level)
# ---------------------------------------------------------------------------

def _batch_bytes(fr):
    return tuple(np.asarray(fr.batch[name]).tobytes()
                 for name in sorted(fr.batch))


def _run_stream(C, m, n, data=None, paged=None, *, local_steps=1,
                start_round=0, rounds=10, planner=None, prefetch=True):
    out = []
    with CohortStream(data, ReshuffleSampler(C, n, seed=1),
                      CohortSampler(C, m, seed=9), local_steps=local_steps,
                      start_round=start_round, planner=planner,
                      prefetch=prefetch, paged=paged) as stream:
        for _ in range(rounds):
            fr = next(stream)
            out.append((fr.round, fr.cohort.tobytes(), fr.cols.tobytes(),
                        _batch_bytes(fr)))
        counts = stream.counts.copy()
    return out, counts


def test_paged_stream_bit_equality_across_epochs(tmp_path):
    """ACCEPTANCE (stream layer): 2+ fleet epochs AND a data-epoch wrap,
    local_steps=2, two modalities — the paged stream's rounds (cohorts,
    cols, every leaf's rows) are byte-identical to the in-RAM stream's,
    and residency stays under the pager's bound throughout."""
    C, m, n = 10, 4, 3
    data = _stacked(C, n=n)
    ds = ClientDataStore.from_stacked(str(tmp_path / "s"), data, shard_size=3)
    pager = LookaheadPager(ds, lookahead=1)
    rounds = 10  # 40 slots / C=10 -> 4 fleet epochs; 8 micro-steps/client
    ram, counts_ram = _run_stream(C, m, n, data=data, local_steps=2,
                                  rounds=rounds)
    paged, counts_pg = _run_stream(C, m, n, paged=pager, local_steps=2,
                                   rounds=rounds)
    assert paged == ram
    assert np.array_equal(counts_pg, counts_ram)
    assert (counts_pg > n).any(), "walk must wrap a data epoch"
    assert pager.resident_nbytes() <= pager.resident_bound_nbytes(m)


def test_paged_stream_resume_mid_walk(tmp_path):
    """ACCEPTANCE (resume): a fresh pager + stream at `start_round=cut`
    replays the tail byte-identically — cursor state is closed-form, page
    residency rebuilds from the walk alone."""
    C, m, n, total, cut = 10, 4, 3, 8, 3
    data = _stacked(C, n=n)
    path = str(tmp_path / "s")
    ClientDataStore.from_stacked(path, data, shard_size=3)
    full, _ = _run_stream(
        C, m, n, paged=LookaheadPager(ClientDataStore.open(path)),
        local_steps=2, rounds=total)
    tail, _ = _run_stream(
        C, m, n, paged=LookaheadPager(ClientDataStore.open(path)),
        local_steps=2, start_round=cut, rounds=total - cut)
    assert tail == full[cut:]
    # and the paged tail == the in-RAM tail (cross-path resume equality)
    ram_tail, _ = _run_stream(C, m, n, data=data, local_steps=2,
                              start_round=cut, rounds=total - cut)
    assert tail == ram_tail


def test_paged_stream_dropout_exactly_once(tmp_path):
    """ACCEPTANCE (chaos): under a seeded dropout planner the paged stream
    matches the in-RAM stream byte-for-byte, non-completers do NOT advance
    page cursors (they re-read the SAME cols when resampled), and a paged
    mid-walk resume replays the planner prefix identically."""
    C, m, n, total, cut = 10, 4, 3, 12, 5
    data = _stacked(C, n=n)
    path = str(tmp_path / "s")
    ClientDataStore.from_stacked(path, data, shard_size=3)
    chaos = ChaosConfig(dropout=0.4, seed=11)
    mk_planner = lambda: AsyncPlanner(m, buffer_k=2, late="drop", chaos=chaos)

    ram, counts_ram = _run_stream(C, m, n, data=data, rounds=total,
                                  planner=mk_planner())
    paged, counts_pg = _run_stream(
        C, m, n, paged=LookaheadPager(ClientDataStore.open(path)),
        rounds=total, planner=mk_planner())
    assert paged == ram
    assert np.array_equal(counts_pg, counts_ram)
    # counts == pure planner replay: only completers advanced. The
    # prefetching stream has PLANNED one round beyond the `total` it
    # emitted, so the replay covers total + 1 rounds.
    cs = CohortSampler(C, m, seed=9)
    planner, replay = mk_planner(), np.zeros(C, np.int64)
    dropped_any = False
    for t in range(total + 1):
        cohort = cs.cohort_for_round(t)
        plan = planner(t, cohort)
        replay[cohort[plan.completes]] += 1
        dropped_any |= not plan.completes.all()
    assert dropped_any, "chaos seed must actually drop someone"
    assert np.array_equal(counts_pg, replay)
    assert replay.sum() < (total + 1) * m
    # paged resume under the planner: prefix replay matches the full run
    tail, _ = _run_stream(
        C, m, n, paged=LookaheadPager(ClientDataStore.open(path)),
        start_round=cut, rounds=total - cut, planner=mk_planner())
    assert tail == paged[cut:]


# ---------------------------------------------------------------------------
# fleet drivers on the pager: production acceptance (mesh level)
# ---------------------------------------------------------------------------

def _driver_fixtures(mesh, method, C, n):
    from test_fleet import _fleet_setup, _population_tokens

    cfg, m, agg, jitted, abstract, shardings, batch_sh = _fleet_setup(
        mesh, method, n=n)
    data = _population_tokens(cfg, C, n, 1, 8)
    return m, agg, jitted, abstract, shardings, batch_sh, data


def _state_snapshot(state, store, C):
    leaves = [np.asarray(a).tobytes() for a in
              jax.tree_util.tree_leaves(jax.device_get(state).params)]
    shifts = [np.asarray(a).tobytes() for a in
              jax.tree_util.tree_leaves(store.gather(np.arange(C)))]
    return leaves, shifts, store.bits.copy(), store.cursor.copy()


@needs_mesh
@pytest.mark.parametrize("method", ["diana", "diana_rr"])
def test_paged_fleet_bit_matches_in_ram(method, mesh_4x2, tmp_path):
    """ACCEPTANCE (driver): a partial-participation `FleetRunner` fed from
    the on-disk pager walks a bitwise-identical trajectory — params, full
    shift tables, bits, cursors — to the in-RAM run, for diana AND
    diana_rr, and the checkpoint manifest records the data-store spec."""
    from repro.core.rules import WIRE_RULES
    from repro.launch import steps

    mesh = mesh_4x2
    # diana_rr's shared-slot wire needs C % m == 0 (no straddling cohorts);
    # diana takes C=10 so round 2 straddles the fleet-epoch boundary
    C = 12 if method == "diana_rr" else 10
    n, total = 3, 5  # 2 fleet epochs either way
    m, agg, jitted, abstract, shardings, batch_sh, data = _driver_fixtures(
        mesh, method, C, n)
    key = jax.random.key(4)
    slotted = method == "diana_rr"

    def run(pager):
        from test_fleet import _tiny_cfg

        store = ClientStateStore.create(
            abstract.params, C, WIRE_RULES[method], n_slots=agg.n_slots,
            dtype=np.float32, shard_size=3)
        with jax.set_mesh(mesh):
            state = jax.device_put(
                steps.init_train_state(jax.random.key(0), _tiny_cfg(), agg,
                                       m, mesh=mesh), shardings)
            with FleetRunner(
                    jitted, abstract, shardings, batch_sh, agg=agg,
                    mesh=mesh, data=None if pager else data,
                    sampler=ReshuffleSampler(
                        C, n, mode="rr_shared" if slotted else "rr", seed=1),
                    cohorts=CohortSampler(C, m, seed=9), store=store,
                    paged=pager) as runner:
                state = runner.run(state, key, total)
                meta = runner.checkpoint_meta()
        return _state_snapshot(state, store, C), meta

    ref, meta_ram = run(None)
    ds = ClientDataStore.from_stacked(str(tmp_path / "s"), data, shard_size=3)
    pager = LookaheadPager(ds, lookahead=1)
    got, meta_pg = run(pager)

    assert got[0] == ref[0], "params diverged"
    assert got[1] == ref[1], "shift tables diverged"
    assert np.array_equal(got[2], ref[2]) and np.array_equal(got[3], ref[3])
    assert "data_store" not in meta_ram
    assert meta_pg["data_store"] == ds.spec()
    assert pager.resident_nbytes() <= pager.resident_bound_nbytes(m)


@needs_mesh
def test_paged_fleet_resume_and_layout_refusal(mesh_4x2, tmp_path):
    """ACCEPTANCE (resume): a paged fleet checkpoint cut mid-walk restores
    bit-exactly through the pager, and `restore_fleet_checkpoint` REFUSES
    (a) a paged checkpoint restored without its data store and (b) a
    mismatched store layout — both before touching any buffers."""
    from repro.checkpoint import (CheckpointError, load_meta,
                                  restore_fleet_checkpoint,
                                  save_fleet_checkpoint)
    from repro.core.rules import WIRE_RULES
    from repro.launch import steps
    from test_fleet import _tiny_cfg

    mesh = mesh_4x2
    C, n, total, cut = 10, 3, 6, 3
    m, agg, jitted, abstract, shardings, batch_sh, data = _driver_fixtures(
        mesh, "diana", C, n)
    ds = ClientDataStore.from_stacked(str(tmp_path / "s"), data, shard_size=3)
    key = jax.random.key(4)
    path = str(tmp_path / "fleet.ckpt")
    mk_store = lambda: ClientStateStore.create(
        abstract.params, C, WIRE_RULES["diana"], dtype=np.float32,
        shard_size=4)
    mk_runner = lambda start, store, pager: FleetRunner(
        jitted, abstract, shardings, batch_sh, agg=agg, mesh=mesh,
        data=None, sampler=ReshuffleSampler(C, n, mode="rr", seed=1),
        cohorts=CohortSampler(C, m, seed=9), store=store,
        start_round=start, paged=pager)

    with jax.set_mesh(mesh):
        state = jax.device_put(
            steps.init_train_state(jax.random.key(0), _tiny_cfg(), agg, m,
                                   mesh=mesh), shardings)
        store = mk_store()
        runner = mk_runner(0, store, LookaheadPager(ds))

        def snap(t, st, metrics):
            if t + 1 == cut:
                save_fleet_checkpoint(
                    path, jax.device_get(st), store, step=t + 1,
                    meta={"fleet": runner.checkpoint_meta()}, data_store=ds)

        with runner:
            state = runner.run(state, key, total, callback=snap)
        ref, ref_store = jax.device_get(state), store

        fm = load_meta(path)["meta"]
        assert fm["data_store_spec"] == ds.spec()
        assert fm["fleet"]["data_store"] == ds.spec()

        # refusal (a): paged checkpoint without its data store
        with pytest.raises(CheckpointError, match="no data store"):
            restore_fleet_checkpoint(path, abstract, shardings, mk_store())
        # refusal (b): a different on-disk layout
        other = ClientDataStore.from_stacked(str(tmp_path / "other"), data,
                                             shard_size=5)
        with pytest.raises(CheckpointError, match="shard_size"):
            restore_fleet_checkpoint(path, abstract, shardings, mk_store(),
                                     data_store=other)

        # the real resume: same layout, fresh pager
        store_b = mk_store()
        state_b = restore_fleet_checkpoint(path, abstract, shardings,
                                           store_b, data_store=ds)
        with mk_runner(fm["fleet"]["round"], store_b,
                       LookaheadPager(ClientDataStore.open(str(
                           tmp_path / "s")))) as runner_b:
            state_b = runner_b.run(state_b, key, total - cut)
        flt = jax.device_get(state_b)

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
def test_paged_async_fleet_under_dropout_bit_matches_ram(mesh_4x2, tmp_path):
    """ACCEPTANCE (async + chaos): the buffered-async driver under seeded
    dropout + injected store faults walks the SAME trajectory paged as
    in-RAM — gather/scatter route through the pager inside `_io_retry`,
    non-completers' page cursors hold still, and the injection schedule is
    unchanged by paging."""
    from repro.core.rules import WIRE_RULES
    from repro.launch import steps
    from test_fleet import _tiny_cfg

    from test_fleet import _fleet_setup, _population_tokens

    mesh = mesh_4x2
    C, n, total = 8, 3, 6
    # elastic step: the async driver feeds variable completer counts
    cfg, m, agg, jitted, abstract, shardings, batch_sh = _fleet_setup(
        mesh, "diana", n=n, elastic=True)
    data = _population_tokens(cfg, C, n, 1, 8)
    chaos = ChaosConfig(dropout=0.2, straggler=0.4, delay=1.0,
                        store_fail=0.3, max_retries=3, seed=5)
    key = jax.random.key(4)

    def run(pager):
        store = ClientStateStore.create(
            abstract.params, C, WIRE_RULES["diana"], dtype=np.float32,
            shard_size=3)
        with jax.set_mesh(mesh):
            state = jax.device_put(
                steps.init_train_state(jax.random.key(0), _tiny_cfg(), agg,
                                       m, mesh=mesh), shardings)
            with AsyncFleetRunner(
                    jitted, abstract, shardings, batch_sh, agg=agg,
                    mesh=mesh, data=None if pager else data,
                    sampler=ReshuffleSampler(C, n, mode="rr", seed=1),
                    cohorts=CohortSampler(C, m, seed=9), store=store,
                    buffer_k=3, late="drop", chaos=chaos,
                    paged=pager) as runner:
                state = runner.run(state, key, total)
        return _state_snapshot(state, store, C), store

    ref, ref_store = run(None)
    ds = ClientDataStore.from_stacked(str(tmp_path / "s"), data, shard_size=3)
    got, got_store = run(LookaheadPager(ds, lookahead=1))

    assert got[0] == ref[0], "params diverged under chaos"
    assert got[1] == ref[1], "shift tables diverged under chaos"
    assert np.array_equal(got[2], ref[2]), "bits diverged"
    assert np.array_equal(got[3], ref[3]), "cursors diverged"
    # dropout really bit: somebody sits below the full walk
    assert ref_store.cursor.sum() < \
        CohortSampler(C, m, seed=9).participation_counts(total).sum()
