"""With the timed path broken underneath, the run reports correct false.

Each fault is planted in the program's entry point that the cell's window
drives (`api.solve`, `api.svd`), and the rest of the run is the harness's
own, off the chip, at a tiny size: a job that hands back its starting
state, half of the rows left out with the rest scaled to stand for them,
an answer altered where it is produced, and (SVD) right singular vectors
out of order with σ, with U formed consistently from them.  No cell spans chips, so none has an
exchange between chips to drop."""
import dataclasses

import jax.numpy as jnp
import pytest

import tiny
from repro import api
from repro.core.distmat import RowMatrix


def solve_unchanged(orig):
    def f(req, **kw):
        res = orig(req, **kw)
        res.x = jnp.zeros_like(res.x)
        return res
    return f


def solve_half(orig):
    """Half the rows left out, the loss's mean taken over the rest."""
    def f(req, **kw):
        A = req.A
        half = A.rows.shape[0] // 2
        return orig(dataclasses.replace(
            req, A=RowMatrix.create(A.rows[:half]),
            b=jnp.asarray(req.b)[:half], lam=req.lam / 2), **kw)
    return f


def solve_altered(orig):
    def f(req, **kw):
        res = orig(req, **kw)
        res.x = res.x.at[0].add(0.01 * jnp.linalg.norm(res.x))
        return res
    return f


def svd_unchanged(orig):
    """The start of the iteration handed back: V the first k unit
    vectors, σ and U from them."""
    def f(req):
        res = orig(req)
        U, s, V = res.factors
        V0 = jnp.eye(V.shape[0], V.shape[1], dtype=jnp.float32)
        AV = req.A.multiply_local(V0).rows
        s0 = jnp.linalg.norm(AV, axis=0)
        res.factors = (dataclasses.replace(U, rows=AV / s0), s0, V0)
        return res
    return f


def svd_half(orig):
    """V and σ from half the rows, σ scaled by √2 to stand for all of
    them, U = A·V·Σ⁻¹ from the whole matrix."""
    def f(req):
        A = req.A
        half = dataclasses.replace(req, A=RowMatrix.create(
            A.rows[:A.rows.shape[0] // 2]))
        res = orig(req)
        _, s, V = orig(half).factors
        s = jnp.asarray(s) * 2 ** 0.5
        U = A.multiply_local(V / s[None, :]).rows
        res.factors = (dataclasses.replace(res.factors[0], rows=U), s, V)
        return res
    return f


def svd_altered(orig):
    def f(req):
        res = orig(req)
        U, s, V = res.factors
        res.factors = (U, jnp.asarray(s).at[0].multiply(1.01), V)
        return res
    return f


def svd_permuted(orig):
    """V's columns in reverse order with σ left in order, and U = A·V·Σ⁻¹
    formed from them, so that A·V = U·Σ still holds."""
    def f(req):
        res = orig(req)
        U, s, V = res.factors
        Vp = V[:, ::-1]
        Up = req.A.multiply_local(Vp / jnp.asarray(s)[None, :]).rows
        res.factors = (dataclasses.replace(U, rows=Up), s, Vp)
        return res
    return f


FAULTS = {
    "dense-solve": [(api, "solve", solve_unchanged),
                    (api, "solve", solve_half),
                    (api, "solve", solve_altered)],
    "dense-svd": [(api, "svd", svd_unchanged), (api, "svd", svd_half),
                  (api, "svd", svd_altered), (api, "svd", svd_permuted)],
}
CASES = [(cell, i) for cell, fs in FAULTS.items() for i in range(len(fs))]
NAMES = ["unchanged", "half", "altered", "permuted"]


@pytest.mark.parametrize("cell,i", CASES,
                         ids=[f"{c}-{NAMES[i]}" for c, i in CASES])
def test_fault_fails_the_check(cell, i, monkeypatch):
    owner, attr, fault = FAULTS[cell][i]
    monkeypatch.setattr(owner, attr, fault(getattr(owner, attr)))
    res = tiny.run(cell)
    assert res["correct"] is False, res["checks"]
