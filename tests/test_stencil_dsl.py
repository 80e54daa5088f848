"""Stencil DSL unit tests: parsing, oracle semantics, Pallas equivalence."""

import dataclasses
import functools

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core.backend import compile_stencil
from repro.core.stencil import DomainSpec, Field, Param, Schedule, gtstencil

compile_jnp = functools.partial(compile_stencil, backend="jnp")
compile_pallas = functools.partial(compile_stencil, backend="pallas-tpu")


@gtstencil
def smagorinsky(vort: Field, delpc: Field, dt: Param):
    with computation(PARALLEL), interval(...):
        vort = dt * (delpc ** 2.0 + vort ** 2.0) ** 0.5


@gtstencil
def flux_region(q: Field, u: Field, flux: Field):
    with computation(PARALLEL), interval(...):
        flux = u * (q[-1, 0, 0] + q[0, 0, 0]) * 0.5
        with horizontal(region[:, 0]):
            flux = u * q


@gtstencil
def thomas(a: Field, b: Field, c: Field, d: Field, x: Field):
    with computation(FORWARD):
        with interval(0, 1):
            c = c / b
            d = d / b
        with interval(1, None):
            c = c / (b - a * c[0, 0, -1])
            d = (d - a * d[0, 0, -1]) / (b - a * c[0, 0, -1])
    with computation(BACKWARD):
        with interval(-1, None):
            x = d
        with interval(0, -1):
            x = d - c * x[0, 0, 1]


@gtstencil
def vertical_integral(delp: Field, pe: Field, ptop: Param):
    with computation(FORWARD):
        with interval(0, 1):
            pe = ptop
        with interval(1, None):
            pe = pe[0, 0, -1] + delp[0, 0, -1]


DOM = DomainSpec(ni=6, nj=5, nk=8, halo=2)


def randf(rng, lo=0.5, hi=1.5):
    return jnp.asarray(rng.uniform(lo, hi, DOM.padded_shape()), jnp.float32)


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def test_parse_structure():
    assert smagorinsky.fields == ("vort", "delpc")
    assert smagorinsky.params == ("dt",)
    assert thomas.is_vertical_solver()
    assert not smagorinsky.is_vertical_solver()
    assert flux_region.max_halo() == 1
    ext = flux_region.extents()
    assert ext["q"][0] == -1


def test_smagorinsky_matches_numpy(rng):
    v, dp = randf(rng), randf(rng)
    out = compile_jnp(smagorinsky, DOM)({"vort": v, "delpc": dp}, {"dt": 0.5})
    h = DOM.halo
    interior = np.s_[:, h:h + DOM.nj, h:h + DOM.ni]
    ref = 0.5 * np.sqrt(np.asarray(dp) ** 2 + np.asarray(v) ** 2)
    np.testing.assert_allclose(np.asarray(out["vort"])[interior],
                               ref[interior], rtol=1e-6)


def test_region_predication(rng):
    q, u = randf(rng), randf(rng)
    flux = jnp.zeros(DOM.padded_shape(), jnp.float32)
    out = compile_jnp(flux_region, DOM)({"q": q, "u": u, "flux": flux})
    h = DOM.halo
    got = np.asarray(out["flux"])
    qn, un = np.asarray(q), np.asarray(u)
    exp = un[:, h:h + DOM.nj, h:h + DOM.ni] * (
        qn[:, h:h + DOM.nj, h - 1:h + DOM.ni - 1]
        + qn[:, h:h + DOM.nj, h:h + DOM.ni]) * 0.5
    exp[:, 0, :] = (un * qn)[:, h, h:h + DOM.ni]
    np.testing.assert_allclose(got[:, h:h + DOM.nj, h:h + DOM.ni], exp,
                               rtol=1e-6)


def test_thomas_solves_tridiagonal(rng):
    a = randf(rng, 0.1, 0.5)
    b = randf(rng, 2.0, 3.0)
    c = randf(rng, 0.1, 0.5)
    d = randf(rng, -1, 1)
    x = jnp.zeros(DOM.padded_shape(), jnp.float32)
    out = compile_jnp(thomas, DOM)(dict(a=a, b=b, c=c, d=d, x=x))
    h = DOM.halo
    xs = np.asarray(out["x"])
    an, bn, cn, dn = (np.asarray(t) for t in (a, b, c, d))
    # residual check: A x = d per column
    for j in range(h, h + DOM.nj):
        for i in range(h, h + DOM.ni):
            xv = xs[:, j, i]
            res = bn[:, j, i] * xv
            res[1:] += an[1:, j, i] * xv[:-1]
            res[:-1] += cn[:-1, j, i] * xv[1:]
            np.testing.assert_allclose(res, dn[:, j, i], rtol=2e-4, atol=2e-4)


def test_forward_integral(rng):
    delp = randf(rng)
    pe = jnp.zeros(DOM.padded_shape(), jnp.float32)
    out = compile_jnp(vertical_integral, DOM)({"delp": delp, "pe": pe},
                                              {"ptop": 2.0})
    h = DOM.halo
    pen = np.asarray(out["pe"])[:, h, h]
    dn = np.asarray(delp)[:, h, h]
    expect = 2.0 + np.concatenate([[0], np.cumsum(dn[:-1])])
    np.testing.assert_allclose(pen, expect, rtol=1e-6)


@pytest.mark.parametrize("stencil,fields,params", [
    (smagorinsky, ("vort", "delpc"), {"dt": 0.5}),
    (flux_region, ("q", "u", "flux"), {}),
    (thomas, ("a", "b", "c", "d", "x"), {}),
])
def test_pallas_matches_jnp(rng, stencil, fields, params):
    fs = {f: randf(rng, 0.5, 2.5) for f in fields}
    o1 = compile_jnp(stencil, DOM)(fs, params)
    o2 = compile_pallas(stencil, DOM)(fs, params)
    for k in o1:
        np.testing.assert_allclose(np.asarray(o1[k]), np.asarray(o2[k]),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("sched,nk", [
    pytest.param(Schedule(block_k=4), 8, id="sched0"),
    pytest.param(Schedule(block_k=0), 8, id="sched1"),
    pytest.param(Schedule(region_strategy="split"), 8, id="sched2"),
    # ragged K slabs: the last slab holds 1 or 5 of 13 levels
    pytest.param(Schedule(block_k=4), 13, id="bk4-nk13"),
    pytest.param(Schedule(block_k=8), 13, id="bk8-nk13"),
    pytest.param(Schedule(block_k=8, region_strategy="split"), 13,
                 id="bk8-split-nk13"),
])
def test_pallas_schedules_equivalent(rng, sched, nk):
    dom = DomainSpec(ni=DOM.ni, nj=DOM.nj, nk=nk, halo=DOM.halo)
    fs = {f: jnp.asarray(rng.uniform(0.5, 1.5, dom.padded_shape()),
                         jnp.float32) for f in ("q", "u", "flux")}
    o1 = compile_jnp(flux_region, dom)(fs)
    o2 = compile_pallas(flux_region, dom, schedule=sched)(fs)
    np.testing.assert_allclose(np.asarray(o1["flux"]),
                               np.asarray(o2["flux"]), rtol=1e-5)
    # K slabs compute what whole columns do, bit for bit
    o3 = compile_pallas(flux_region, dom, schedule=dataclasses.replace(
        sched, block_k=0))(fs)
    np.testing.assert_array_equal(np.asarray(o3["flux"]),
                                  np.asarray(o2["flux"]))


def test_vertical_carry_storage_equivalent(rng):
    fs = {f: randf(rng, 0.5, 2.5) for f in ("a", "b", "c", "d", "x")}
    o1 = compile_pallas(thomas, DOM, schedule=Schedule(
        carry_storage="vreg", k_as_grid=False))(fs)
    o2 = compile_pallas(thomas, DOM, schedule=Schedule(
        carry_storage="vmem", k_as_grid=False))(fs)
    np.testing.assert_allclose(np.asarray(o1["x"]), np.asarray(o2["x"]),
                               rtol=1e-6)
