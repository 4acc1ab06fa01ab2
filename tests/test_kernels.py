"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracles,
sweeping shapes/dtypes, plus hypothesis property tests on the oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp_compat import given, settings, st

from repro.kernels import ops, ref

jax.config.update("jax_platform_name", "cpu")

SHAPES = [(1, 8, 16), (2, 16, 16), (3, 40, 72), (2, 128, 128), (1, 130, 60)]


def _rand_problem(key, B, n, m):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    S = jax.random.uniform(k1, (B, n, m))
    S = S / S.sum(-1, keepdims=True)
    Q = jax.random.bernoulli(k2, 0.3, (n, n)).astype(jnp.uint8)
    Q = jnp.triu(Q, k=1)  # DAG
    G = jax.random.bernoulli(k3, 0.4, (m, m)).astype(jnp.uint8)
    G = jnp.triu(G, k=1)
    mask = jax.random.bernoulli(k4, 0.8, (n, m)).astype(jnp.uint8)
    # guarantee at least one feasible entry per row to exercise normalize
    mask = mask.at[:, 0].set(1)
    return S, Q, G, mask


@pytest.mark.parametrize("B,n,m", SHAPES)
def test_edge_fitness_matches_ref(B, n, m):
    S, Q, G, _ = _rand_problem(jax.random.PRNGKey(0), B, n, m)
    got = ops.edge_fitness(S, Q, G, backend="interpret")
    want = ops.edge_fitness(S, Q, G, backend="ref")
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("B,n,m", SHAPES)
def test_edge_fitness_quantized_matches_ref(B, n, m):
    S, Q, G, _ = _rand_problem(jax.random.PRNGKey(1), B, n, m)
    Sq = ref.quantize_s(S)
    got = ops.edge_fitness_quantized(Sq, Q, G, backend="interpret")
    want = ops.edge_fitness_quantized(Sq, Q, G, backend="ref")
    np.testing.assert_allclose(got, np.asarray(want, dtype=np.float64),
                               rtol=1e-3)


@pytest.mark.parametrize("B,n,m", SHAPES)
def test_ullmann_refine_matches_ref(B, n, m):
    key = jax.random.PRNGKey(2)
    _, Q, G, mask = _rand_problem(key, B, n, m)
    M = jnp.broadcast_to(mask, (B, n, m)).astype(jnp.uint8)
    got = ops.ullmann_refine_step(M, Q, G, backend="interpret")
    want = ops.ullmann_refine_step(M, Q, G, backend="ref")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("B,n,m", SHAPES)
def test_pso_update_matches_ref(B, n, m):
    key = jax.random.PRNGKey(3)
    S, Q, G, mask = _rand_problem(key, B, n, m)
    ks = jax.random.split(key, 5)
    V = jax.random.normal(ks[0], (B, n, m)) * 0.1
    S_local = S
    S_star = S[0]
    S_bar = S.mean(0)
    r = jax.random.uniform(ks[1], (B, 3))
    hyper = dict(omega=0.7, c1=1.4, c2=1.4, c3=0.6, v_max=0.5)
    s_got, v_got = ops.pso_update(S, V, S_local, S_star, S_bar, mask, r,
                                  backend="interpret", **hyper)
    s_want, v_want = ops.pso_update(S, V, S_local, S_star, S_bar, mask, r,
                                    backend="ref", **hyper)
    np.testing.assert_allclose(s_got, s_want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(v_got, v_want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("n,m", [(8, 16), (16, 16), (40, 72), (130, 60)])
def test_greedy_project_matches_ref(n, m):
    key = jax.random.PRNGKey(4)
    S, _, _, mask = _rand_problem(key, 1, n, m)
    got = ops.greedy_project(S[0], mask, backend="interpret")
    want = ops.greedy_project(S[0], mask, backend="ref")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n,m", [(8, 16), (40, 72), (130, 60)])
def test_masked_argmax_matches_ref(n, m):
    key = jax.random.PRNGKey(5)
    X = jax.random.normal(key, (n, m))
    mask = jax.random.bernoulli(jax.random.PRNGKey(6), 0.5, (n, m)
                                ).astype(jnp.uint8)
    vg, ig = ops.masked_argmax(X, mask, backend="interpret")
    vw, iw = ops.masked_argmax(X, mask, backend="ref")
    np.testing.assert_allclose(vg, vw, rtol=1e-6)
    assert int(ig) == int(iw)


# ------------------------- property tests (oracles) ------------------------

@pytest.mark.slow
@settings(max_examples=25, deadline=None)
@given(st.integers(2, 12), st.integers(2, 20), st.randoms())
def test_pso_update_invariants(n, m, rnd):
    """After any update: rows are stochastic, masked entries zero, S >= 0."""
    seed = rnd.randint(0, 2**31 - 1)
    key = jax.random.PRNGKey(seed)
    S, _, _, mask = _rand_problem(key, 1, n, m)
    V = jax.random.normal(key, (1, n, m))
    r = jax.random.uniform(key, (1, 3))
    s_new, _ = ops.pso_update(S, V, S, S[0], S[0], mask, r, omega=0.7,
                              c1=1.5, c2=1.5, c3=0.5, backend="ref")
    s_new = np.asarray(s_new[0])
    maskb = np.asarray(mask, dtype=bool)
    assert (s_new >= -1e-7).all()
    assert np.abs(s_new[~maskb]).max(initial=0.0) < 1e-7
    np.testing.assert_allclose(s_new.sum(-1), 1.0, atol=1e-4)


@pytest.mark.slow
@settings(max_examples=25, deadline=None)
@given(st.integers(2, 10), st.integers(2, 14), st.randoms())
def test_refine_never_adds_candidates(n, m, rnd):
    seed = rnd.randint(0, 2**31 - 1)
    key = jax.random.PRNGKey(seed)
    _, Q, G, mask = _rand_problem(key, 1, n, m)
    M = mask[None].astype(jnp.uint8)
    M2 = ops.ullmann_refine_step(M, Q, G, backend="ref")
    assert (np.asarray(M2) <= np.asarray(M)).all()


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 8), st.randoms())
def test_perfect_match_zero_residual(n, rnd):
    """Mapping a graph onto itself with identity S has fitness 0 when the
    target has exactly the query edges (monomorphism residual counts both
    missing and extra edges; self-map of Q onto Q is exact)."""
    seed = rnd.randint(0, 2**31 - 1)
    key = jax.random.PRNGKey(seed)
    Q = jnp.triu(jax.random.bernoulli(key, 0.4, (n, n)), 1).astype(jnp.uint8)
    S = jnp.eye(n)[None]
    f = ops.edge_fitness(S, Q, Q, backend="ref")
    np.testing.assert_allclose(f, 0.0, atol=1e-6)


def test_quantized_fitness_ordering_matches_float():
    """PSO only needs the *ordering* of fitness values: check uint8 path
    preserves ranking of clearly-separated particles."""
    key = jax.random.PRNGKey(7)
    S, Q, G, _ = _rand_problem(key, 8, 24, 32)
    f_float = np.asarray(ops.edge_fitness(S, Q, G, backend="ref"))
    Sq = ref.quantize_s(S)
    f_q = np.asarray(ops.edge_fitness_quantized(Sq, Q, G, backend="ref"),
                     dtype=np.float64)
    # compare orderings of pairs separated by > quantization noise
    order_f = np.argsort(f_float)
    f_scaled = f_q / (255.0 ** 4)  # back to float units
    for a, b in zip(order_f[:-1], order_f[1:]):
        if f_float[b] - f_float[a] > 1.0:  # > uint8 quantization noise band
            assert f_scaled[b] > f_scaled[a]


@pytest.mark.parametrize("terms", [16, 144, 256])
def test_int_dot_wide_is_exact_integer_matmul(terms):
    """The MXU form of the quantized fitness's S G Sᵀ: 8-bit S against
    partial sums up to 255·terms, bitwise the int32 product; longer
    contractions than it is exact for are refused."""
    from repro.kernels.mxu import int_dot, int_dot_wide
    k1, k2 = jax.random.split(jax.random.PRNGKey(terms))
    S = jax.random.randint(k1, (24, terms), 0, 256, jnp.int32)
    G = jax.random.bernoulli(k2, 0.5, (terms, terms)).astype(jnp.int32)
    dims = (((1,), (0,)), ((), ()))
    SG = int_dot(S, G, dims)
    np.testing.assert_array_equal(SG, S @ G)
    nt = (((1,), (1,)), ((), ()))
    np.testing.assert_array_equal(int_dot_wide(SG, S, nt), SG @ S.T)
    with pytest.raises(ValueError):
        int_dot_wide(jnp.zeros((8, 259), jnp.int32),
                      jnp.zeros((8, 259), jnp.int32), nt)
