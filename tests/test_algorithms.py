"""Behavioural tests of the 13 federated drivers on a strongly-convex task.

These encode the paper's THEOREM-level claims as assertions:
  - every method decreases the objective (sanity);
  - DIANA-RR converges to the exact optimum with constant stepsize while
    Q-RR stalls at a compression-variance neighborhood (Thm 1 vs Thm 2);
  - DIANA-NASTYA beats Q-NASTYA the same way (Thm 3 vs Thm 4);
  - Q-RR and QSGD end up at comparable suboptimality (the paper's negative
    result, Sec. 2.1);
  - NASTYA with eta = gamma*n reproduces FedRR exactly (Corollary 3 remark);
  - shift layouts: DIANA 1/worker, DIANA-RR n/worker.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compression.ops import Identity, RandK
from repro.core.algorithms import ALGORITHMS, init_algorithm, make_epoch_fn
from repro.data.logreg import make_federated_logreg

PROBLEM = make_federated_logreg(m=8, n_batches=6, batch=6, d=16, cond=20.0, seed=3)
LOSS = PROBLEM.loss_fn()
P0 = {"w": jnp.zeros((PROBLEM.d,))}
COMP = RandK(fraction=0.25)


def run(name, epochs=150, gamma=None, eta=None, alpha=None, comp=None, seed=0):
    spec = ALGORITHMS[name]
    if comp is None:
        # error feedback needs a CONTRACTIVE compressor (Top-k); the unbiased
        # scaled Rand-k has omega > 1 variance and EF theory does not apply
        from repro.compression.ops import TopK
        comp = TopK(fraction=0.25) if spec.shift_mode == "ef" else COMP
    gamma = gamma if gamma is not None else 0.5 / PROBLEM.l_max
    if spec.family == "local":
        gamma = gamma / PROBLEM.n
        eta = eta if eta is not None else gamma * PROBLEM.n
    spec, epoch = make_epoch_fn(
        name, LOSS, comp if spec.default_compressed else Identity(),
        gamma=gamma, eta=eta, alpha=alpha,
    )
    st = init_algorithm(spec, P0, PROBLEM.m, PROBLEM.n)
    ep = jax.jit(epoch)
    key = jax.random.PRNGKey(seed)
    for _ in range(epochs):
        key, k = jax.random.split(key)
        st = ep(st, PROBLEM.data, k)
    return st


@pytest.mark.parametrize("name", sorted(ALGORITHMS))
def test_decreases_objective(name):
    st = run(name, epochs=30)
    f0 = PROBLEM.full_objective(np.zeros(PROBLEM.d))
    fT = PROBLEM.full_objective(np.asarray(st.params["w"]))
    assert np.isfinite(fT)
    assert fT < f0 - 0.1 * (f0 - PROBLEM.f_star)


def test_diana_rr_beats_q_rr():
    """Thm 2 vs Thm 1: DIANA-RR kills the O(gamma*omega) neighborhood."""
    sub_q = PROBLEM.suboptimality(run("q_rr", epochs=400).params["w"])
    sub_d = PROBLEM.suboptimality(run("diana_rr", epochs=400).params["w"])
    assert sub_d < sub_q / 100


def test_q_rr_matches_qsgd():
    """The paper's negative result: no RR benefit under naive compression."""
    sub_q_rr = PROBLEM.suboptimality(run("q_rr", epochs=120).params["w"])
    sub_qsgd = PROBLEM.suboptimality(run("qsgd", epochs=120).params["w"])
    ratio = sub_q_rr / sub_qsgd
    assert 0.2 < ratio < 5.0  # same order — neither dominates


def test_diana_nastya_beats_q_nastya():
    """Thm 3 vs Thm 4: with gamma -> 0 the only floor left in Q-NASTYA is the
    O(eta*omega/M) quantization term, which DIANA-NASTYA removes. We use a
    tiny local stepsize to suppress the (shared) client-drift term and a harsh
    compressor so the omega-term dominates."""
    harsh = RandK(fraction=0.1)  # omega = 9
    eta = 1.0 / PROBLEM.l_max
    gamma = eta / (20 * PROBLEM.n)
    sub_q = PROBLEM.suboptimality(
        run("q_nastya", epochs=800, gamma=gamma * PROBLEM.n, eta=eta, comp=harsh).params["w"]
    )
    sub_d = PROBLEM.suboptimality(
        run("diana_nastya", epochs=800, gamma=gamma * PROBLEM.n, eta=eta, comp=harsh).params["w"]
    )
    assert sub_d < sub_q / 5


def test_nastya_eta_gamma_n_is_fedrr():
    """With eta = gamma*n and identity compression NASTYA == FedRR exactly."""
    a = run("nastya", epochs=5, seed=11)
    b = run("fedrr", epochs=5, seed=11)
    np.testing.assert_allclose(np.asarray(a.params["w"]), np.asarray(b.params["w"]), rtol=1e-6)


def test_shift_layouts():
    m, n = PROBLEM.m, PROBLEM.n
    st = init_algorithm(ALGORITHMS["diana"], P0, m, n)
    assert st.shifts["w"].shape == (m, PROBLEM.d)
    st = init_algorithm(ALGORITHMS["diana_rr"], P0, m, n)
    assert st.shifts["w"].shape == (m, n, PROBLEM.d)
    st = init_algorithm(ALGORITHMS["q_rr"], P0, m, n)
    assert st.shifts is None


def test_rounds_and_bits_accounting():
    st_nl = run("q_rr", epochs=3)
    assert int(st_nl.rounds) == 3 * PROBLEM.n
    st_l = run("q_nastya", epochs=3, eta=0.1 / PROBLEM.l_max)
    assert int(st_l.rounds) == 3
    # compressed methods send fewer bits than uncompressed at equal rounds
    st_rr = run("rr", epochs=3)
    assert float(st_nl.bits) < float(st_rr.bits)


def test_rr_beats_sgd_late():
    """Classic RR advantage (no compression): smaller neighborhood."""
    sub_rr = PROBLEM.suboptimality(run("rr", epochs=200).params["w"])
    sub_sgd = PROBLEM.suboptimality(run("sgd", epochs=200).params["w"])
    assert sub_rr < sub_sgd


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_diana_rr_neighborhood_scales_as_gamma_squared(seed):
    """Thm 2: DIANA-RR's only residual term is 2*gamma^2*sigma_rad^2/mu in
    E||x_T - x*||^2 — halving gamma should shrink it ~4x (vs the O(gamma)
    floor of Q-RR, Thm 1). Each stepsize runs epochs proportional to
    1/gamma, so both runs sit on their floor, and the floor is read as the
    float64 squared distance to x* (the float32 objective rounds at ~1e-7,
    the size of the floor itself). We check the floor drops superlinearly
    in gamma and is itself tiny in absolute terms, on every seed."""
    def floor(gamma):
        st = run("diana_rr", epochs=int(400 / gamma),
                 gamma=gamma / PROBLEM.l_max, seed=seed)
        w = np.asarray(st.params["w"], np.float64)
        return float(np.sum((w - PROBLEM.x_star) ** 2)), w

    dist_g, w_g = floor(1.0)
    dist_g2, _ = floor(0.5)
    assert PROBLEM.suboptimality(w_g) < 1e-4  # deep convergence, omega = 3
    assert dist_g2 < dist_g / 2.5  # superlinear shrinkage with gamma


def test_error_feedback_fixes_topk():
    """Beyond-paper: Top-k is biased — naked it stalls/diverges in the
    heterogeneous setting, with error feedback it converges (Stich et al.
    2018, the remedy the paper's related work points to)."""
    import jax
    import jax.numpy as jnp

    from repro.compression.ops import TopK
    from repro.core.algorithms import init_algorithm, make_epoch_fn
    from repro.data.logreg import make_federated_logreg

    problem = make_federated_logreg(m=10, n_batches=5, batch=10, d=40,
                                    cond=50.0, seed=3, heterogeneous=True)
    loss = problem.loss_fn()
    comp = TopK(fraction=0.1)
    gamma = 0.5 / problem.l_max

    def run(name, epochs=300):
        spec, epoch = make_epoch_fn(name, loss, comp, gamma=gamma, alpha=1.0)
        st = init_algorithm(spec, {"w": jnp.zeros((problem.d,))}, problem.m,
                            problem.n)
        ep = jax.jit(epoch)
        key = jax.random.PRNGKey(0)
        for e in range(epochs):
            key, k = jax.random.split(key)
            st = ep(st, problem.data, k)
        return problem.suboptimality(st.params["w"])

    ef = run("ef_topk_rr")
    naked = run("q_rr")  # same Top-k compressor, no error memory
    assert ef < 5e-3, f"EF Top-k failed to converge: {ef}"
    assert ef < naked * 0.5, (ef, naked)


def test_fedstate_bits_lo_default_matches_init_state_dtype():
    """FedState's NamedTuple default for bits_lo must be a strongly-typed
    f32 scalar like init_state builds — a bare Python 0.0 made tree maps
    over hand-built states promote (f64 leaves under numpy semantics)."""
    import numpy as np

    from repro.core.api import FedState, init_state

    hand = FedState(params={"w": jnp.zeros((2,))}, shifts=None,
                    server_h=None, rounds=jnp.zeros((), jnp.int32),
                    bits=jnp.zeros((), jnp.float32))
    ref = init_state({"w": jnp.zeros((2,))})
    assert np.asarray(hand.bits_lo).dtype == np.float32
    assert np.asarray(hand.bits_lo).shape == np.asarray(ref.bits_lo).shape
    summed = jax.tree.map(lambda a, b: jnp.add(a, b), hand, ref)
    assert summed.bits_lo.dtype == jnp.float32
