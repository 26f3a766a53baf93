"""Hypothesis property tests on protocol invariants (fast, pure-jnp)."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import lsh, neighbor, ranking
from repro.core.chain import fnv1a_commit
from repro.kernels import ops


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**16), st.integers(3, 12), st.integers(2, 6))
def test_distance_matrix_metric_properties(seed, m, words):
    """Hamming over packed codes: symmetric, zero diagonal, bounded,
    triangle inequality (it's a true metric)."""
    key = jax.random.PRNGKey(seed)
    bits = jax.random.bernoulli(key, 0.5, (m, words * 32))
    codes = ops.pack_bits(jnp.where(bits, 1.0, -1.0))
    d = np.asarray(lsh.distance_matrix(codes, use_kernel=False))
    assert (d == d.T).all()
    assert (np.diag(d) == 0).all()
    assert (d <= words * 32).all() and (d >= 0).all()
    for i in range(m):
        for j in range(m):
            assert (d[i] + d[j] >= d[i, j]).all()  # vectorized triangle


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**16), st.floats(0.1, 10.0))
def test_weights_monotone_in_distance(seed, gamma):
    """Equal rank scores -> closer peers always weigh more (Eq. 8)."""
    key = jax.random.PRNGKey(seed)
    m = 6
    d = jax.random.uniform(key, (m, m))
    d = (d + d.T) / 2 * (1 - jnp.eye(m))
    s = jnp.ones((m,))
    w = np.asarray(neighbor.selection_weights(s, d, gamma))
    dn = np.asarray(d)
    for i in range(m):
        js = [j for j in range(m) if j != i]
        order_w = sorted(js, key=lambda j: -w[i, j])
        order_d = sorted(js, key=lambda j: dn[i, j])
        assert order_w == order_d


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**16))
def test_ranking_scores_ignore_padding(seed):
    key = jax.random.PRNGKey(seed)
    r = jax.random.randint(key, (5, 3), 0, 6).astype(jnp.int32)
    s1 = ranking.ranking_scores(r, 6, top_k=2)
    padded = jnp.concatenate([r, -jnp.ones((5, 2), jnp.int32)], axis=1)
    s2 = ranking.ranking_scores(padded, 6, top_k=2)
    assert np.allclose(np.asarray(s1), np.asarray(s2))


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 2**16), st.integers(1, 8))
def test_commitment_distinguishes_orderings(seed, n):
    """Rankings are order-sensitive: any permutation that changes the
    sequence changes the commitment (Eq. 9 binding)."""
    key = jax.random.PRNGKey(seed)
    r = jax.random.permutation(key, jnp.arange(n + 1, dtype=jnp.int32))[None]
    c1 = fnv1a_commit(r)
    r2 = jnp.roll(r, 1, axis=1)
    if not bool(jnp.all(r == r2)):
        assert not bool(jnp.all(fnv1a_commit(r2) == c1))


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**16))
def test_sharded_lsh_equals_full_projection(seed):
    """Beyond-paper sharded LSH: sum of per-shard partial projections ==
    projection of the full vector (linearity), asserted under
    jax.shard_map on a 1-device mesh."""
    from repro.kernels.ref import lsh_project_sums_ref
    key = jax.random.PRNGKey(seed)
    n = 4096
    x = jax.random.normal(key, (n,))
    mesh = jax.make_mesh((1,), ("model",))
    fn = jax.shard_map(
        lambda v: lsh.sharded_lsh_code(v, 7, 128, "model"),
        mesh=mesh, in_specs=jax.sharding.PartitionSpec("model"),
        out_specs=jax.sharding.PartitionSpec(), check_vma=False)
    code_sharded = fn(x)
    code_full = ops.pack_bits(lsh_project_sums_ref(x, 7, bits=128))
    assert bool(jnp.all(code_sharded == code_full))
