"""Pairgen Pallas kernel vs jnp oracle: shape sweeps + properties."""
import numpy as np
import pytest

from repro.core import mining
from repro.kernels.tspm_pairgen import ops, pairgen, ref
from tests.conftest import random_dbmart


@pytest.mark.parametrize("P,E", [(1, 8), (3, 16), (8, 48), (16, 130), (7, 129)])
def test_pairgen_shapes(P, E):
    db = random_dbmart(np.random.default_rng(P * 1000 + E),
                       n_patients=P, max_events=E)
    got = ops.pairgen(db.phenx, db.date, db.nevents, interpret=True)
    want = mining.mine_dense(db.phenx, db.date, db.nevents)
    m = np.asarray(want.mask)
    assert (np.asarray(got.mask) == m).all()
    assert (np.asarray(got.seq)[m] == np.asarray(want.seq)[m]).all()
    assert (np.asarray(got.dur)[m] == np.asarray(want.dur)[m]).all()


@pytest.mark.parametrize("codec", ["bit", "paper"])
@pytest.mark.parametrize("fuse", [False, True])
def test_pairgen_codecs_and_fusion(codec, fuse):
    db = random_dbmart(np.random.default_rng(5), n_patients=6, max_events=20)
    got = ops.pairgen(db.phenx, db.date, db.nevents, codec=codec,
                      fuse_duration=fuse, interpret=True)
    want = mining.mine_dense(db.phenx, db.date, db.nevents, codec=codec,
                             fuse_duration=fuse)
    m = np.asarray(want.mask)
    assert (np.asarray(got.seq)[m] == np.asarray(want.seq)[m]).all()


@pytest.mark.parametrize("pb,tile", [(1, 128), (2, 128), (8, 128), (8, 256)])
def test_pairgen_block_shapes(pb, tile):
    db = random_dbmart(np.random.default_rng(9), n_patients=8, max_events=64)
    got = ops.pairgen(db.phenx, db.date, db.nevents, pb=pb, tile=tile,
                      interpret=True)
    want = mining.mine_dense(db.phenx, db.date, db.nevents)
    m = np.asarray(want.mask)
    assert (np.asarray(got.seq)[m] == np.asarray(want.seq)[m]).all()


def test_planes_ref_matches_planes_kernel():
    db = random_dbmart(np.random.default_rng(2), n_patients=8, max_events=32)
    E = 128
    ph = np.zeros((8, E), np.int32)
    dt = np.zeros((8, E), np.int32)
    ph[:, :32] = db.phenx[:, :32]
    dt[:, :32] = db.date[:, :32]
    s, e, d, m = pairgen.pairgen_planes(ph, dt, db.nevents, pb=8, ti=128,
                                        tj=128, interpret=True)
    sr, er, dr, mr = ref.pairgen_planes_ref(ph, dt, db.nevents)
    assert (np.asarray(m) == np.asarray(mr)).all()
    assert (np.asarray(s) == np.asarray(sr)).all()
    assert (np.asarray(e) == np.asarray(er)).all()
    assert (np.asarray(d) == np.asarray(dr)).all()
