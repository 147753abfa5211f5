"""The training cell's output check: the program passes it, and the
control and each fault the cell can have fail it (CPU, tiny widths, the
limits at that size in ``tiny.LIMITS``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import reference, train
from bench.lib.common import report_checks
from bench.tests import tiny


@pytest.fixture(scope="module")
def cell():
    return tiny.train_cell()


def _run(cell):
    out = train.run(cell, tiny.SEED, 0.5, False, tiny.now())
    assert out["host"]["window_compiles"] == 0
    assert out["host"]["window_cache_loads"] == 0
    return report_checks(tiny.checks(out["readings"]))


def test_program_passes(cell):
    assert _run(cell)


def test_control_in_fp8_fails(cell):
    tr = train.Trainer(cell)
    docs = tr.corpus(tiny.SEED)
    it = iter(tr.loader(docs, tiny.SEED))
    batches, off = train.corpus_rows(
        docs, [next(it) for _ in range(train.CHECK_STEPS)])
    assert off == 0
    ref = train.reference_steps(cell.config, cell.traffic, tiny.SEED,
                                batches, "highest")
    ctrl = train.reference_steps(cell.config, cell.traffic, tiny.SEED,
                                 batches, "fp8")
    assert not report_checks(tiny.checks(train.compare(ctrl, ref)))


def test_step_that_returns_its_state_unchanged_fails(cell, monkeypatch):
    import repro.core.tl_step as tl_step
    make = tl_step.make_train_step

    def broken(*a, **kw):
        step = make(*a, **kw)

        def same_state(params, opt_state, batch):
            _, _, loss = step(params, opt_state, batch)
            return params, opt_state, loss
        return same_state
    monkeypatch.setattr(tl_step, "make_train_step", broken)
    import repro.launch.engine as engine
    monkeypatch.setattr(engine, "make_train_step", broken)
    assert not _run(cell)


def test_half_of_the_batch_left_out_fails(cell, monkeypatch):
    import repro.core.tl_step as tl_step
    ce = tl_step.cross_entropy

    def half(logits, targets, mask=None):
        n = logits.shape[0] // 2
        return ce(logits[:n], targets[:n])
    monkeypatch.setattr(tl_step, "cross_entropy", half)
    assert not _run(cell)


def test_token_altered_where_it_is_fed_fails(cell, monkeypatch):
    from repro.data.pipeline import VirtualBatchLoader
    feed = VirtualBatchLoader.__iter__
    vocab = cell.config["vocab_size"]

    def altered(self):
        for b in feed(self):
            tok = b["tokens"].copy()
            tok[0, 5] = (tok[0, 5] + 1) % vocab
            yield dict(b, tokens=tok)
    monkeypatch.setattr(VirtualBatchLoader, "__iter__", altered)
    assert not _run(cell)


def test_corpus_rows_takes_the_benchmarks_own_documents():
    docs = np.arange(5 * 9, dtype=np.int32).reshape(5, 9)
    fed = [{"tokens": docs[[3, 1], :-1], "targets": docs[[3, 1], 1:]},
           {"tokens": docs[[0], :-1], "targets": docs[[0], 1:]}]
    got, off = train.corpus_rows(docs, fed)
    assert off == 0
    np.testing.assert_array_equal(got[0]["tokens"], docs[[3, 1], :-1])
    np.testing.assert_array_equal(got[1]["targets"], docs[[0], 1:])
    bad_tok = docs[[2, 2, 4], :-1].copy()
    bad_tok[2, 3] += 1
    got, off = train.corpus_rows(
        docs, [{"tokens": bad_tok, "targets": docs[[2, 2, 4], 1:]}])
    # row 2 fed twice, row 4 altered: only the first row of 2 is kept
    assert off == 2
    np.testing.assert_array_equal(got[0]["tokens"], docs[[2], :-1])


def test_fp8_matmul_rounds_forward_and_backward():
    a = jax.random.normal(jax.random.PRNGKey(0), (3, 16))
    b = jax.random.normal(jax.random.PRNGKey(1), (16, 5))
    g = jax.random.normal(jax.random.PRNGKey(2), (3, 5))
    out, vjp = jax.vjp(reference._fp8_einsum("ik,kj->ij"), a, b)
    q = reference._fp8
    np.testing.assert_allclose(out, q(a, (1,)) @ q(b, (0,)), rtol=1e-5, atol=1e-6)
    da, db = vjp(g)
    np.testing.assert_allclose(da, q(g, (1,)) @ q(b, (1,)).T, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(db, q(a, (0,)).T @ q(g, (0,)), rtol=1e-5, atol=1e-6)
    # e4m3 keeps 3 bits of mantissa: a few percent off, never exact
    rel = jnp.abs(out - a @ b) / jnp.abs(a @ b).max()
    assert 1e-3 < float(rel.max()) < 0.2
