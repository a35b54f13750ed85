"""The block-diffusion objective's draw (lib/train.py) and its loss
(references/common.py weighted_xent): from the seed alone, the mask id
kept out of the clean ids, the weights 1/t exactly where a token is
masked, a block's masked share following its t, and the program's side
and the reference's handed the same arrays."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import program as P
from benchmarks.lib import train
from benchmarks.references.common import mean_xent, weighted_xent
from benchmarks.tests import tiny

SEED = 3000000037           # the driver's seeds pass 2**31
CELL = "block-diffusion-tiny.train.b2-s64"
VOCAB, MASK, L, T_MIN = 256, 255, 4, 0.1


def draw(seed, batch=2, seq=64, n=3, mask=MASK):
    return train.make_diffusion_batches(seed, VOCAB, batch, seq, n, L,
                                        T_MIN, mask)


def test_the_seed_gives_the_arrays():
    a, b, c = draw(SEED), draw(SEED), draw(SEED + 1)
    for x, y in zip(a, b):
        for u, v in zip(x, y):
            np.testing.assert_array_equal(u, v)
    assert not all(np.array_equal(u, v) for x, y in zip(a, c)
                   for u, v in zip(x, y))
    noised, clean, weights = a[0]
    assert noised.dtype == clean.dtype == np.int32
    assert weights.dtype == np.float32
    assert noised.shape == clean.shape == weights.shape == (2, 64)
    # every row differs, and the batches of one draw differ
    assert not np.array_equal(clean[0], clean[1])
    assert not np.array_equal(a[0][1], a[1][1])


@pytest.mark.parametrize("mask", [0, 100, MASK])
def test_the_mask_id_is_never_clean(mask):
    clean = np.concatenate([b[1] for b in draw(SEED, 8, 256, 8, mask)])
    assert not (clean == mask).any()
    assert clean.min() >= 0 and clean.max() < VOCAB
    # every other id is drawn
    assert len(np.unique(clean)) == VOCAB - 1


def drawn_t(seed, batch, seq, n):
    """Each token's t, per batch, read back from make_batches' stream as
    the draw consumes it."""
    rng = np.random.RandomState((seed + 1) % (2 ** 32))
    out = []
    for _ in range(n):
        rng.randint(0, VOCAB - 1, size=(batch, seq))
        out.append(np.repeat(rng.uniform(T_MIN, 1.0, (batch, seq // L)), L,
                             axis=1))
        rng.random_sample((batch, seq))
    return out


def test_weights_are_one_over_t_where_masked():
    batches = draw(SEED, n=3)
    for (noised, clean, weights), t in zip(batches, drawn_t(SEED, 2, 64, 3)):
        masked = noised == MASK
        assert masked.any() and (~masked).any()
        np.testing.assert_array_equal(weights[masked],
                                      (1.0 / t[masked]).astype(np.float32))
        assert (weights[~masked] == 0).all()
        np.testing.assert_array_equal(noised[~masked], clean[~masked])


def test_the_masked_share_of_a_block_follows_its_t():
    """Over 16,384 blocks of 4: in each tenth of [t_min, 1) the share of
    tokens masked is the mean t of its blocks."""
    (noised, _, _), = draw(SEED, batch=64, seq=1024, n=1)
    t = drawn_t(SEED, 64, 1024, 1)[0].reshape(64, -1, L)[..., 0]
    share = (noised == MASK).reshape(64, -1, L).mean(-1)
    for lo in np.arange(T_MIN, 1.0, 0.1):
        sel = (t >= lo) & (t < lo + 0.1)
        assert sel.sum() > 1000
        assert share[sel].mean() == pytest.approx(t[sel].mean(), abs=0.02)
    assert np.corrcoef(t.ravel(), share.ravel())[0, 1] > 0.4


def test_weighted_xent_with_unit_weights_is_mean_xent():
    key = jax.random.PRNGKey(0)
    logits = jax.random.normal(key, (3, 16, 50), jnp.float32) * 3
    labels = jax.random.randint(jax.random.fold_in(key, 1), (3, 16), 0, 50)
    ones = jnp.ones((3, 16), jnp.float32)
    assert float(weighted_xent(logits, labels, ones)) == \
        float(mean_xent(logits, labels))
    # weights 0 on one row of three and 3/2 on the others: their mean
    w = ones.at[:1].set(0.0).at[1:].set(1.5)
    assert float(weighted_xent(logits, labels, w)) == pytest.approx(
        float(mean_xent(logits[1:], labels[1:])), rel=1e-6)


def test_both_sides_are_handed_the_same_arrays(monkeypatch):
    """What the program's feed stages and what the reference scores are
    the arrays the runner drew, and the next-token cells' batches are
    make_batches' as they were."""
    import itertools
    tiny.patch(monkeypatch)
    cell, config, _, _ = P.load_cell(CELL)
    batches = train.host_batches(cell, config, SEED, train.CHECK_STEPS)
    assert all(isinstance(b, tuple) and len(b) == 3 for b in batches)
    it = train.feed(batches)
    try:
        for fed, drawn in zip(itertools.islice(it, 4), batches + batches):
            for f, d in zip(fed, drawn):
                np.testing.assert_array_equal(np.asarray(f.numpy()), d)
    finally:
        it.close()
    handed = []
    from benchmarks.lib import reftrain
    real = reftrain.compiled

    def spy(fn, *args, **kw):
        exe = real(fn, *args, **kw)
        if fn.__name__ != "loss_and_grad":
            return exe
        return lambda *a: (handed.append(a[1]), exe(*a))[1]
    monkeypatch.setattr(reftrain, "compiled", spy)
    reftrain.reference_train(P.reference_of(config), config, SEED, batches,
                             cell["optimizer"], micro=cell["batch"])
    assert len(handed) == len(batches)
    for got, drawn in zip(handed, batches):
        for g, d in zip(got, drawn):
            np.testing.assert_array_equal(np.asarray(g), d)
    nt = dict(cell, objective=None)
    np.testing.assert_array_equal(
        train.host_batches(nt, config, SEED, 2)[1],
        train.make_batches(SEED, config["vocab_size"], cell["batch"],
                           cell["seq"], 2)[1])
