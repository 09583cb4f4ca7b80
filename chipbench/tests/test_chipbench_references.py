"""The plain references against the literal recurrences they restate."""
import numpy as np
import pytest

from chipbench.harness import Manifest

M = Manifest()


@pytest.mark.parametrize("n,penalty,seed", [(8, 10, 0), (33, 3, 1)])
def test_nw_rows_equal_the_cell_by_cell_recurrence(n, penalty, seed):
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    s = rng.integers(-3, 4, (n, n)).astype(np.float32)
    m = np.zeros((n + 1, n + 1), np.float32)
    m[0, :] = m[:, 0] = -penalty * np.arange(n + 1)
    for i in range(1, n + 1):
        for j in range(1, n + 1):
            m[i, j] = max(m[i - 1, j - 1] + s[i - 1, j - 1],
                          m[i, j - 1] - penalty, m[i - 1, j] - penalty)
    got = M.module("kernels", "nw").reference(
        jnp.asarray(s), spec={"n": n, "penalty": penalty})
    np.testing.assert_array_equal(np.asarray(got), m)


def test_lud_reference_factors_the_matrix():
    import jax.numpy as jnp
    rng = np.random.default_rng(2)
    n = 16
    a = rng.normal(size=(n, n)).astype(np.float32) + n * np.eye(n)
    lu = np.asarray(M.module("kernels", "lud").reference(
        jnp.asarray(a), spec={"n": n}))
    lower = np.tril(lu, -1) + np.eye(n)
    upper = np.triu(lu)
    np.testing.assert_allclose(lower @ upper, a, rtol=1e-5, atol=1e-4)


def test_qwen2_reference_attention_is_causal():
    """Logits at a position do not depend on later tokens, so padding a
    sequence at its end leaves them unchanged."""
    import jax
    import jax.numpy as jnp
    import os
    data = os.path.join(os.path.dirname(__file__), "data")
    c = Manifest(os.path.join(data, "BENCHMARK.json"),
                 search=(data,)).json("configs", "tiny-dense")
    ref = M.module("models", "qwen2")
    w = ref.make_weights(c, jax.random.PRNGKey(3))
    toks = jnp.asarray(np.random.default_rng(4).integers(0, 512, 32),
                       jnp.int32)
    at = jnp.arange(8, 16, dtype=jnp.int32)
    a = ref.forward(w, c, toks, at)
    b = ref.forward(w, c, toks.at[20:].set(7), at)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    assert a.shape == (8, 512)
