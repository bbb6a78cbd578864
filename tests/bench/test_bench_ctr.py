"""The benchmark's generator copy gives the program's batches."""
import numpy as np
import pytest

import smoke  # noqa: F401  (puts the repository root on sys.path)
from bench.ctr import CTR, Spec
from repro.configs._recsys_common import (CRITEO_VOCABS, XDEEPFM_VOCABS,
                                          smoke_vocabs)
from repro.data.synthetic_ctr import CTRGenerator, CTRSpec

CASES = [
    (smoke_vocabs(26), 13, 5),
    (tuple(v // 1000 + 1 for v in CRITEO_VOCABS), 13, 2**31 + 77),
    (tuple(v // 1000 + 1 for v in XDEEPFM_VOCABS), 0, 3),
]


@pytest.mark.parametrize("vocabs,n_dense,seed", CASES)
def test_batches_equal_program_generator(vocabs, n_dense, seed):
    ours = CTR(Spec(vocabs, n_dense, seed))
    theirs = CTRGenerator(CTRSpec(n_fields=len(vocabs), n_dense=n_dense,
                                  vocab_sizes=vocabs, seed=seed))
    for idx in (0, 9, 10_000_000):
        a, b = ours.batch(300, idx), theirs.batch(300, idx)
        assert a.keys() == b.keys()
        for k in a:
            np.testing.assert_array_equal(a[k], b[k])


def test_signature_rows_equal_program_rows():
    vocabs = smoke_vocabs(26)
    ours = CTR(Spec(vocabs, 13, 8))
    theirs = CTRGenerator(CTRSpec(n_fields=26, n_dense=13, vocab_sizes=vocabs,
                                  seed=8))
    np.testing.assert_array_equal(
        ours.signature_rows(5000), np.stack(list(
            theirs.rows_for_signatures(5000))))


def test_uniform_values_equal_program_generator():
    vocabs = smoke_vocabs(12)
    ours = CTR(Spec(vocabs, 0, 4, value_dist="uniform"))
    theirs = CTRGenerator(CTRSpec(n_fields=12, n_dense=0, vocab_sizes=vocabs,
                                  seed=4, value_dist="uniform"))
    np.testing.assert_array_equal(ours.batch(200, 1)["sparse"],
                                  theirs.batch(200, 1)["sparse"])


def test_large_fields_repeat_few_ids_in_a_batch():
    """The train mix as PERF.md describes it: in a field of Criteo's
    largest vocabulary nearly every id of a 4,096 batch is distinct, and a
    field with fewer values than clusters shows all of them."""
    b = CTR(Spec((CRITEO_VOCABS[0], 3), 0, 2**31 + 5)).batch(4096, 0)
    assert len(np.unique(b["sparse"][:, 0])) > 0.99 * 4096
    assert len(np.unique(b["sparse"][:, 1])) == 3
