"""Admission prefill unembeds only each request's last true position.

``lm_forward(..., logits_at=pos)`` gathers row ``pos[b]`` after the final
norm and runs the head on those rows alone, so the paged prefill's head
GEMM has one row a request in place of the whole padded bucket.  The
served token and the finite-logit flag must be the ones the full head's
row at ``true_len - 1`` gives, bit for bit, under the Pallas LUT kernels
(interpret mode here; ``tests/test_obs.py`` counts one head row an
admission): no simulated bit depends on a launch's row count,
since the 2-D brick folds k in the fixed order ``FOLD_2D`` whatever its
row tile.  The autotune cache is pinned empty, so a tuned entry cannot
give the two row counts two folds.

One bucket and a one-layer model keep the lowering short: interpret mode
spends about a second on each LUT kernel.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.core.policy import NumericsPolicy
from repro.kernels import autotune
from repro.models.transformer import init_lm, init_paged_lm_caches, lm_forward
from repro.serve.scheduler import _merge_control, make_paged_prefill

AMSIM = NumericsPolicy(mode="amsim", multiplier="afm16")
BUCKET, PAGE = 8, 4
TRUE_LENS = (1, BUCKET // 2, BUCKET)


@pytest.fixture(scope="module", autouse=True)
def hermetic(tmp_path_factory):
    mp = pytest.MonkeyPatch()
    mp.setenv("REPRO_AUTOTUNE_CACHE",
              str(tmp_path_factory.mktemp("autotune") / "none.json"))
    autotune.reload_cache()
    yield
    mp.undo()
    autotune.reload_cache()


@pytest.fixture(scope="module")
def setup():
    cfg = reduced(get_arch("granite-3-2b"), n_layers=1)
    return cfg, init_lm(jax.random.PRNGKey(0), cfg)


def test_paged_prefill_matches_full_head_row(setup):
    """Requests of true length 1, P/2 and P in one bucket of P: the
    served token, the ``ok`` flag and the unembedded row equal those of
    the full (B, P, vocab) logits at ``true_len - 1``."""
    cfg, params = setup
    B, pages = len(TRUE_LENS), BUCKET // PAGE
    true_len = jnp.asarray(TRUE_LENS, jnp.int32)
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, BUCKET), 1,
                              cfg.vocab)
    toks = jnp.where(jnp.arange(BUCKET) < true_len[:, None], toks, 0)
    ptab = 1 + jnp.arange(B * pages, dtype=jnp.int32).reshape(B, pages)
    pool = init_paged_lm_caches(cfg, B * pages + 1, PAGE)

    @jax.jit
    def forward(params, toks, logits_at):
        merged = _merge_control(pool, ptab, jnp.ones((B,), bool),
                                jnp.zeros((B,), jnp.int32))
        return lm_forward(params, toks, cfg, AMSIM, caches=merged,
                          logits_at=logits_at)[0]

    full = forward(params, toks, None)
    assert full.shape == (B, BUCKET, cfg.vocab)
    want = jnp.take_along_axis(full, (true_len - 1)[:, None, None], axis=1)
    rows = forward(params, toks, true_len - 1)
    assert rows.shape == (B, 1, cfg.vocab)
    np.testing.assert_array_equal(np.asarray(rows), np.asarray(want))

    nxt, ok, _ = jax.jit(make_paged_prefill(cfg, AMSIM))(
        params, toks, true_len, ptab, pool)
    np.testing.assert_array_equal(
        np.asarray(nxt), np.asarray(jnp.argmax(want, axis=-1)))
    np.testing.assert_array_equal(
        np.asarray(ok), np.asarray(jnp.isfinite(want[:, 0]).all(axis=-1)))
    assert nxt.shape == (B, 1) and bool(ok.all())

